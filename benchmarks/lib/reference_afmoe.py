"""Plain reference for the ``afmoe`` decoder (Trinity-Mini's ``config.json``):
window and full grouped-KV attention, a dense gated MLP on the leading
layers and, on the others, sigmoid-routed experts beside a shared one, with
a balancing bias carried as state: forward, loss, gradient, AdamW and the
bias update.

The equations, on x in R^{T x d} (d ``hidden_size``, H ``heads`` query heads
on Hkv ``kv_heads`` KV heads of D ``head_dim``, W ``sliding_window``,
eps ``rms_norm_eps``; RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * scale). What
``config.json`` has no key for is in the configuration file's ``assumed``,
each item naming the family's ``afmoe`` modeling code as its ground:

* Embedding: x0 = E[tokens] * sqrt(d) (``mup_enabled``).
* Layer i, sandwich norms, two residuals:
  h = x + N2(Attn_i(N1(x))); y = h + N4(Mlp_i(N3(h))), with N1..N4 the
  layer's four RMSNorms (``ln1``, ``ln1_post``, ``ln2``, ``ln2_post``: the
  family's ``input``, ``post_attention``, ``pre_mlp``, ``post_mlp``).
* Attn_i(u): q = u Wq -> [T, H, D], k = u Wk, v = u Wv -> [T, Hkv, D],
  g = u Wg -> [T, H * D]; q and k through an RMSNorm over the D dims of each
  head (learned scales ``q_norm``, ``k_norm``), THEN the rotary embedding
  over all D dims (halves rotated, theta ``rope_theta``) ONLY where
  ``layer_types[i]`` is ``sliding_attention`` (a ``full_attention`` layer
  has no position: NoPE). Query head j reads KV head j // (H / Hkv); scores
  q.k * D^-0.5; key s is visible to query t iff s <= t and, on a sliding
  layer, t - s < W (the query's own token is one of the W); softmax in
  float32; o = P v -> [T, H * D]; the output gate o <- o * sigmoid(g);
  out = o Wo. No bias anywhere.
* Mlp_i, i < ``dense_layers``: W2(silu(W1 z) * W3 z) at width ``d_ff``.
* Mlp_i otherwise: Shared(z) + sum_{e in S(z)} gate_e * Expert_e(z), Shared
  and every Expert_e the same gated form at width ``d_expert``
  (``shared`` = 1 shared expert). s = sigmoid(z Wr) in float32, Wr [d, E];
  S(z) = the ``top_k`` largest of s + b (b the layer's bias [E]: state, not
  a parameter, zero at the start); gate_e = ``route_scale`` * s_e /
  (sum_{j in S(z)} s_j + 1e-20) (``route_norm``): the bias chooses, it
  never weighs. No group limit (``n_group`` = ``topk_group`` = 1), nothing
  dropped, no auxiliary loss. This chip holds experts ``expert_first ..
  expert_first + experts_held``: the sum runs over the held experts a token
  chose and what the absent ones would add is left out (guide
  model-configs, section 4), while S(z), the gates' normaliser and the
  counts are over all E. Computed densely: every held expert on every
  token, times a gate that is zero where the token did not choose it.
* Bias update, once a step after the optimizer's, from the step's counts
  n [E] of token-choices per expert: delta = ``balance_coeff`` *
  sign(mean(n) - n); delta <- delta - mean(delta); b <- b + delta.
* Out: RMSNorm, untied head [vocab, d], mean next-token cross entropy over
  the (sliced) vocabulary.
* Weights: normal(``init_std``) for every matrix and the embedding, norm
  scales 1.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``, no
kernel. It imports nothing of the program and is handed nothing the program
made: weights and batches are made again from the seed. What keeps it inside
a chip's memory at T = 8,192 and changes no arithmetic: the T x T scores are
taken ``q_block`` queries at a time with the window as a mask, every block
is recomputed in the backward pass (``jax.checkpoint``), the held experts
are walked one at a time.

``precision``: ``"float32"`` is the reference proper; ``"float8"`` is the
CONTROL (operands of every matmul, router included, rounded to
``float8_e4m3fn``), the nearest precision below the bfloat16 the
configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.lib.reference_gpt2 import (PRECISIONS, _is_spec, _mm,
                                           leaf_norms, path_dict)
# The same plain pieces as the sparse family's reference: RMSNorm, the rotary
# embedding over the whole head (halves rotated), and the held experts'
# dense share (every held expert on every token, times its gate).
from benchmarks.lib.reference_sparse_moe import moe_share, rms_norm, rope

__all__ = ["PRECISIONS", "SLIDING", "FULL", "sizes_from_config",
           "param_shapes", "make_params", "zero_biases", "loss_sum",
           "train_steps", "leaf_norms", "path_dict", "attention", "route",
           "moe_share", "moe", "bias_update"]

SLIDING, FULL = "sliding_attention", "full_attention"


def sizes_from_config(cfg: dict) -> dict:
    """The sizes this file needs, from the published key names. ``layers``
    is the depth as run (``num_hidden_layers`` stays the published 32) and
    ``layer_types`` its first ``layers`` entries, as a tuple."""
    layers = cfg["layers"]
    kinds = tuple(cfg["layer_types"][:layers])
    if len(kinds) != layers or set(kinds) - {SLIDING, FULL}:
        raise ValueError(f"layer_types {kinds} for {layers} layers")
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing is not written here")
    return dict(
        layers=layers, layer_types=kinds, d_model=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], vocab=cfg["vocab_size"],
        window=cfg["sliding_window"], dense_layers=cfg["num_dense_layers"],
        d_ff=cfg["intermediate_size"], experts=cfg["num_experts"],
        experts_held=cfg["num_local_experts"],
        expert_first=cfg.get("first_local_expert", 0),
        top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        shared=cfg["num_shared_experts"], route_norm=bool(cfg["route_norm"]),
        route_scale=float(cfg["route_scale"]),
        balance_coeff=float(cfg["load_balance_coeff"]),
        embed_scale=(cfg["hidden_size"] ** 0.5 if cfg.get("mup_enabled")
                     else 1.0),
        eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        init_std=cfg.get("initializer_range", 0.02))


def routed_layers(s: dict) -> list:
    return [f"h{i}" for i in range(s["dense_layers"], s["layers"])]


def param_shapes(s: dict) -> dict:
    """The parameter tree (name -> (shape, init)): normal(init_std) for
    every matrix and the embedding, ones for every RMSNorm scale; no bias
    anywhere (the routers' selection biases are state: ``zero_biases``)."""
    d, D, std = s["d_model"], s["head_dim"], s["init_std"]
    H, Hk = s["heads"], s["kv_heads"]

    def w(*shape):
        return (shape, ("normal", std))

    def ones(n):
        return {"scale": ((n,), ("ones",))}

    def gated(f, *lead):
        return {"w1": w(*lead, d, f), "w3": w(*lead, d, f),
                "w2": w(*lead, f, d)}

    tree = {"embed": w(s["vocab"], d), "head": w(s["vocab"], d),
            "ln_f": ones(d)}
    for i in range(s["layers"]):
        layer = {
            "ln1": ones(d), "ln1_post": ones(d), "ln2": ones(d),
            "ln2_post": ones(d),
            "attn": {"wq": w(d, H * D), "wk": w(d, Hk * D),
                     "wv": w(d, Hk * D), "wg": w(d, H * D),
                     "wo": w(H * D, d), "q_norm": ((D,), ("ones",)),
                     "k_norm": ((D,), ("ones",))}}
        if i < s["dense_layers"]:
            layer["mlp"] = gated(s["d_ff"])
        else:
            layer["moe"] = {"router": w(d, s["experts"]),
                            **gated(s["d_expert"], s["experts_held"])}
            if s["shared"]:
                layer["moe"]["shared"] = gated(s["shared"] * s["d_expert"])
        tree[f"h{i}"] = layer
    return tree


def make_params(seed, s: dict):
    """float32 weights from ``seed`` (a uint32 array or an int): every leaf
    its own draw of standard normals, in its own shape, keyed by its
    position in the flattened tree (as lib/reference_sparse_moe.py makes
    them, and for its reason). Jit it: every leaf is made on the device."""
    key = jax.random.key(jnp.asarray(seed, jnp.uint32))
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(s), is_leaf=_is_spec)
    leaves = [
        init[1] * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        if init[0] == "normal" else jnp.ones(shape, jnp.float32)
        for i, (_, (shape, init)) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def zero_biases(s: dict) -> dict:
    """The routers' selection biases at the start, in the tree the program
    keeps them in: {layer: {"moe": {"bias": [E]}}}."""
    return {name: {"moe": {"bias": jnp.zeros((s["experts"],), jnp.float32)}}
            for name in routed_layers(s)}


# -- the pieces of a block ----------------------------------------------------

def attention(q, k, v, window, mm, q_block: int):
    """o [T, H, D]: softmax attention of query t over keys s <= t, and
    t - s < ``window`` where it is not None, ``q_block`` queries at a
    time."""
    T, H, D = q.shape
    Hk = k.shape[1]
    bq = min(q_block, T)
    if T % bq:
        raise ValueError(f"q_block {bq} does not divide T {T}")

    @jax.checkpoint
    def one(args):
        qb, t = args
        ahead = t[:, None] - jnp.arange(T)[None, :]              # t - s
        seen = ahead >= 0
        if window is not None:
            seen &= ahead < window
        qg = qb.reshape(bq, Hk, H // Hk, D)
        scores = mm("qkgd,skd->kgqs", qg, k) * D ** -0.5
        probs = jax.nn.softmax(
            jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return mm("kgqs,skd->qkgd", probs, v).reshape(bq, H, D)

    n = T // bq
    o = jax.lax.map(one, (q.reshape(n, bq, H, D),
                          jnp.arange(T).reshape(n, bq)))
    return o.reshape(T, H, D)


def gated_mlp(z, p, mm):
    return mm("tf,fc->tc", jax.nn.silu(mm("tc,cf->tf", z, p["w1"]))
              * mm("tc,cf->tf", z, p["w3"]), p["w2"])


def route(z, router, bias, s: dict, mm):
    """(experts [T, K], gates [T, K]): the ``top_k`` largest of
    sigmoid(z W_r) + bias; the gates are the scores alone, over their sum,
    times ``route_scale``."""
    scores = jax.nn.sigmoid(mm("tc,ce->te", z, router))
    _, experts = jax.lax.top_k(scores + bias, s["top_k"])
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    if s["route_norm"]:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return experts, gates * s["route_scale"]


def moe(z, p, bias, s: dict, mm):
    """(Shared(z) + the held experts' part, counts [E] of token-choices
    per expert over ALL the experts)."""
    experts, gates = route(z, p["router"], bias, s, mm)
    y = moe_share(z, p, experts, gates, s["expert_first"], mm)
    if s["shared"]:
        y = y + gated_mlp(z, p["shared"], mm)
    counts = jnp.zeros((s["experts"],), jnp.float32).at[
        experts.reshape(-1)].add(1.0)
    return y, counts


def bias_update(bias, counts, coeff: float):
    delta = coeff * jnp.sign(counts.mean() - counts)
    return bias + delta - delta.mean()


def _block(x, p, bias, i: int, s: dict, mm, q_block: int):
    """Layer ``i`` on x [T, d]: (y, counts [E] or None)."""
    T, d = x.shape
    H, Hk, D = s["heads"], s["kv_heads"], s["head_dim"]
    sliding = s["layer_types"][i] == SLIDING
    u = rms_norm(x, p["ln1"]["scale"], s["eps"])
    a = p["attn"]
    q = rms_norm(mm("tc,cf->tf", u, a["wq"]).reshape(T, H, D), a["q_norm"],
                 s["eps"])
    k = rms_norm(mm("tc,cf->tf", u, a["wk"]).reshape(T, Hk, D), a["k_norm"],
                 s["eps"])
    v = mm("tc,cf->tf", u, a["wv"]).reshape(T, Hk, D)
    if sliding:
        q, k = rope(q, s["rope_theta"]), rope(k, s["rope_theta"])
    o = attention(q, k, v, s["window"] if sliding else None, mm, q_block)
    o = o.reshape(T, H * D) * jax.nn.sigmoid(mm("tc,cf->tf", u, a["wg"]))
    h = x + rms_norm(mm("tf,fc->tc", o, a["wo"]), p["ln1_post"]["scale"],
                     s["eps"])
    z = rms_norm(h, p["ln2"]["scale"], s["eps"])
    if i < s["dense_layers"]:
        m, counts = gated_mlp(z, p["mlp"], mm), None
    else:
        m, counts = moe(z, p["moe"], bias, s, mm)
    return h + rms_norm(m, p["ln2_post"]["scale"], s["eps"]), counts


def loss_sum(params, biases, tokens, s: dict, precision: str = "float32",
             q_block: int = 256):
    """(summed next-token cross entropy over ``tokens`` [rows, T + 1],
    {layer: counts [E]} summed over the rows)."""
    mm = _mm(precision)

    @jax.checkpoint
    def head(x, y_ids):
        x = rms_norm(x, params["ln_f"]["scale"], s["eps"])
        logp = jax.nn.log_softmax(mm("tc,vc->tv", x, params["head"]), -1)
        return -jnp.take_along_axis(logp, y_ids[:, None], axis=-1).sum()

    def row(carry, toks):
        total, counts = carry
        x = params["embed"][toks[:-1]] * s["embed_scale"]
        for i in range(s["layers"]):
            name = f"h{i}"
            bias = (biases[name]["moe"]["bias"] if name in biases else None)
            x, n = jax.checkpoint(functools.partial(
                _block, i=i, s=s, mm=mm, q_block=q_block))(
                x, params[name], bias)
            if n is not None:
                counts = {**counts, name: counts[name] + n}
        return (total + head(x, toks[1:]), counts), None

    zero = {name: jnp.zeros((s["experts"],), jnp.float32)
            for name in routed_layers(s)}
    (total, counts), _ = jax.lax.scan(row, (jnp.float32(0), zero), tokens)
    return total, counts


def train_steps(seed, batches, s: dict, opt: dict, micro_rows: int,
                precision: str = "float32", q_block: int = 256):
    """Follow the first ``len(batches)`` steps of training from ``seed``:
    AdamW behind a clip by the global norm, then the bias update, written
    out in full. ``batches`` is [steps, rows, T + 1]; a step's gradient is
    the mean over all its rows' tokens (rows are walked one at a time
    inside ``loss_sum``; ``micro_rows`` is taken as the whole batch).
    Returns what lib/reference_gpt2.py ``train_steps`` returns, with the
    routers' biases among the leaves of ``delta_norm`` (they start at
    zero: a bias's change is the bias)."""
    steps, rows, width = batches.shape
    del micro_rows
    n_tok = rows * (width - 1)
    grad_fn = jax.value_and_grad(functools.partial(
        loss_sum, s=s, precision=precision, q_block=q_block), has_aux=True)

    def one_step(carry, tokens):
        p, m, v, t, b = carry
        (loss, counts), g = grad_fn(p, b, tokens)
        loss, g = loss / n_tok, jax.tree.map(lambda a: a / n_tok, g)
        norms = leaf_norms(g)
        gnorm = jnp.sqrt(sum(n ** 2 for n in norms.values()))
        clip = jnp.where(gnorm < opt["clip_norm"], 1.0,
                         opt["clip_norm"] / gnorm)
        t = t + 1
        c1, c2 = 1 - opt["b1"] ** t, 1 - opt["b2"] ** t
        m = jax.tree.map(
            lambda a, b_: opt["b1"] * a + (1 - opt["b1"]) * clip * b_, m, g)
        v = jax.tree.map(
            lambda a, b_: opt["b2"] * a + (1 - opt["b2"]) * (clip * b_) ** 2,
            v, g)
        p = jax.tree.map(
            lambda w, a, b_: w - opt["lr"] * (
                (a / c1) / (jnp.sqrt(b_ / c2) + opt["eps"])
                + opt["weight_decay"] * w), p, m, v)
        b = {name: {"moe": {"bias": bias_update(
            tree["moe"]["bias"], counts[name], s["balance_coeff"])}}
            for name, tree in b.items()}
        return (p, m, v, t, b), (loss, norms)

    p0 = make_params(seed, s)
    zeros = jax.tree.map(jnp.zeros_like, p0)
    (p, _, _, _, b), (losses, norms) = jax.lax.scan(
        one_step, (p0, zeros, zeros, jnp.float32(0), zero_biases(s)),
        batches)
    delta = leaf_norms(jax.tree.map(jnp.subtract, p, make_params(seed, s)))
    return {"loss": losses,
            "grad_norm": jax.tree.map(lambda a: a[0], norms),
            "delta_norm": {**delta, **leaf_norms(b)}}
