"""Plain reference for the GPT-2 family: forward, loss, gradient and AdamW.

Radford et al. 2019 as ``config.json`` of ``openai-community/gpt2*`` states
it: learned positions, pre-LayerNorm blocks, multi-head attention with a
causal mask, GELU (tanh form, ``gelu_new``) MLP, head tied to ``wte``.
Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``,
dense attention, no kernel, no cache. It imports nothing of the program and
is handed nothing the program made: it makes the weights and the batches
again from the seed, with the benchmark's own functions.

Departure from the published description, also written in the configuration
files: dropout is 0 (the program has none).

``precision`` selects the arithmetic of every matmul:

* ``"float32"``: the reference proper.
* ``"float8"``: the CONTROL. Operands of every matmul are rounded to
  ``float8_e4m3fn`` first, the nearest precision below the bfloat16 the
  configurations state. Put in the program's place it has to come out as
  not correct (benchmarks/limits.py reads it on the chip).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "float8")


def sizes_from_config(cfg: dict) -> dict:
    """The sizes this file needs, from the published key names."""
    d = cfg["n_embd"]
    return dict(layers=cfg["n_layer"], d_model=d, heads=cfg["n_head"],
                d_ff=cfg.get("n_inner") or 4 * d, vocab=cfg["vocab_size"],
                positions=cfg["n_positions"],
                ln_eps=cfg["layer_norm_epsilon"],
                init_std=cfg["initializer_range"])


def param_shapes(s: dict) -> dict:
    """The parameter tree (name -> shape) with its init: ("normal", std),
    ("ones",) or ("zeros",). GPT-2's init: normal(0.02) everywhere, the two
    residual projections scaled by 1/sqrt(2 * layers), LayerNorm at
    identity, biases zero."""
    d, f, std = s["d_model"], s["d_ff"], s["init_std"]
    res_std = std / (2 * s["layers"]) ** 0.5

    def dense(i, o, w_std):
        return {"kernel": ((i, o), ("normal", w_std)),
                "bias": ((o,), ("zeros",))}

    def ln():
        return {"scale": ((d,), ("ones",)), "bias": ((d,), ("zeros",))}

    tree = {"wte": ((s["vocab"], d), ("normal", std)),
            "wpe": ((s["positions"], d), ("normal", std)),
            "ln_f": ln()}
    for i in range(s["layers"]):
        tree[f"h{i}"] = {
            "ln1": ln(),
            "attn": {"qkv": dense(d, 3 * d, std),
                     "proj": dense(d, d, res_std)},
            "ln2": ln(),
            "mlp": {"Dense_0": dense(d, f, std),
                    "Dense_1": dense(f, d, res_std)},
        }
    return tree


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], tuple)


def make_params(seed, s: dict):
    """float32 weights from ``seed`` (a uint32 array or an int). One draw of
    standard normals for all the weights, cut into the leaves in the tree's
    order and scaled: one random op, so the program that makes 355M weights
    compiles in seconds. Jit it: every leaf is made on the device."""
    key = jax.random.key(jnp.asarray(seed, jnp.uint32))
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(s), is_leaf=_is_spec)
    specs = [spec for _, spec in flat]
    total = sum(int(np.prod(shape)) for shape, init in specs
                if init[0] == "normal")
    draws = jax.random.normal(key, (total,), jnp.float32)
    leaves, at = [], 0
    for shape, init in specs:
        if init[0] == "normal":
            n = int(np.prod(shape))
            leaves.append(init[1] * draws[at:at + n].reshape(shape))
            at += n
        elif init[0] == "ones":
            leaves.append(jnp.ones(shape, jnp.float32))
        else:
            leaves.append(jnp.zeros(shape, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _mm(precision: str):
    """``einsum`` with the matmul arithmetic ``precision`` names."""
    if precision == "float32":
        return functools.partial(jnp.einsum, precision="highest",
                                 preferred_element_type=jnp.float32)
    if precision == "float8":
        def low(spec, a, b):
            a8 = a.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
            b8 = b.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
            return jnp.einsum(spec, a8, b8,
                              preferred_element_type=jnp.float32)
        return low
    raise ValueError(f"precision {precision!r} not in {PRECISIONS}")


def _layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1 + jnp.tanh(
        (2 / np.pi) ** 0.5 * (x + 0.044715 * x ** 3)))


def _block(x, p, s, mm):
    B, T, d = x.shape
    H = s["heads"]
    D = d // H
    h = _layer_norm(x, p["ln1"], s["ln_eps"])
    qkv = mm("btc,cf->btf", h, p["attn"]["qkv"]["kernel"]) \
        + p["attn"]["qkv"]["bias"]
    q, k, v = (a.reshape(B, T, H, D) for a in jnp.split(qkv, 3, axis=-1))
    scores = mm("bqhd,bkhd->bhqk", q, k) * D ** -0.5
    mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    att = mm("bhqk,bkhd->bqhd", probs, v).reshape(B, T, d)
    x = x + mm("btc,cf->btf", att, p["attn"]["proj"]["kernel"]) \
        + p["attn"]["proj"]["bias"]
    h = _layer_norm(x, p["ln2"], s["ln_eps"])
    h = _gelu_new(mm("btc,cf->btf", h, p["mlp"]["Dense_0"]["kernel"])
                  + p["mlp"]["Dense_0"]["bias"])
    return x + mm("btf,fc->btc", h, p["mlp"]["Dense_1"]["kernel"]) \
        + p["mlp"]["Dense_1"]["bias"]


def loss_sum(params, tokens, s: dict, precision: str = "float32"):
    """Summed next-token cross entropy over ``tokens`` [rows, T + 1]."""
    mm = _mm(precision)
    x_ids, y_ids = tokens[:, :-1], tokens[:, 1:]
    T = x_ids.shape[1]
    x = params["wte"][x_ids] + params["wpe"][:T][None]
    # The layers as one scan over their stacked weights, each block
    # recomputed in the backward pass: the reference's own economy of
    # compile time and memory, so that float32 activations of the timed
    # batch fit beside the program's cache entry. The arithmetic of a
    # layer is _block's, unchanged.
    block = jax.checkpoint(functools.partial(_block, s=s, mm=mm))
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves),
                           *(params[f"h{i}"] for i in range(s["layers"])))
    x, _ = jax.lax.scan(lambda h, p: (block(h, p), None), x, stacked)
    x = _layer_norm(x, params["ln_f"], s["ln_eps"])
    logits = mm("btc,vc->btv", x, params["wte"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, y_ids[..., None], axis=-1).sum()


def path_dict(tree) -> dict:
    """{path: leaf} with paths as '/'-joined keys ('h0/attn/qkv/kernel')."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", getattr(k, "name", k)))
                     for k in path): leaf for path, leaf in flat}


def leaf_norms(tree) -> dict:
    """{path: L2 norm} over the leaves."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in path_dict(tree).items()}


def train_steps(seed, batches, s: dict, opt: dict, micro_rows: int,
                precision: str = "float32"):
    """Follow the first ``len(batches)`` steps of training from ``seed``:
    AdamW behind a clip by the global norm, written out in full. ``batches``
    is [steps, rows, T + 1]; each step's gradient is the mean over all its
    rows, taken ``micro_rows`` at a time. Returns the loss of each step, the
    per-leaf norms of the first step's gradient (before the clip, as the
    optimizer is handed it) and the per-leaf norms of the parameters' change
    over all the steps."""
    steps, rows, width = batches.shape
    if rows % micro_rows:
        raise ValueError(f"{rows} rows do not divide into micro-batches of "
                         f"{micro_rows}")
    n_tok = rows * (width - 1)
    grad_fn = jax.value_and_grad(
        functools.partial(loss_sum, s=s, precision=precision))

    def one_step(carry, tokens):
        p, m, v, t = carry
        micro = tokens.reshape(rows // micro_rows, micro_rows, width)

        def acc(c, mb):
            loss, g = grad_fn(p, mb)
            return (c[0] + loss, jax.tree.map(jnp.add, c[1], g)), None

        zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, p))
        (loss, g), _ = jax.lax.scan(acc, zero, micro)
        loss, g = loss / n_tok, jax.tree.map(lambda a: a / n_tok, g)
        norms = leaf_norms(g)
        gnorm = jnp.sqrt(sum(n ** 2 for n in norms.values()))
        clip = jnp.where(gnorm < opt["clip_norm"], 1.0,
                         opt["clip_norm"] / gnorm)
        g = jax.tree.map(lambda a: a * clip, g)
        t = t + 1
        m = jax.tree.map(lambda a, b: opt["b1"] * a + (1 - opt["b1"]) * b,
                         m, g)
        v = jax.tree.map(
            lambda a, b: opt["b2"] * a + (1 - opt["b2"]) * b * b, v, g)
        c1, c2 = 1 - opt["b1"] ** t, 1 - opt["b2"] ** t
        p = jax.tree.map(
            lambda w, a, b: w - opt["lr"] * (
                (a / c1) / (jnp.sqrt(b / c2) + opt["eps"])
                + opt["weight_decay"] * w), p, m, v)
        return (p, m, v, t), (loss, norms)

    p0 = make_params(seed, s)
    zeros = jax.tree.map(jnp.zeros_like, p0)
    (p, _, _, _), (losses, norms) = jax.lax.scan(
        one_step, (p0, zeros, zeros, jnp.float32(0)), batches)
    # Made again, not kept: one copy fewer alive through the steps.
    delta = leaf_norms(jax.tree.map(jnp.subtract, p, make_params(seed, s)))
    return {"loss": losses,
            "grad_norm": jax.tree.map(lambda a: a[0], norms),
            "delta_norm": delta}
