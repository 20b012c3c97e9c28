"""Plain reference for the SmallThinker decoder (``config.json`` of
PowerInfer/SmallThinker-21BA3B-Instruct): a NoPE full-attention layer and
sliding-window layers with rotary position, 7 query heads a KV head, and on
every layer ReLU-gated experts whose router reads the block's INPUT, before
attention: forward, loss, gradient and AdamW.

The equations, on x in R^{T x d} (d ``hidden_size``, H ``heads`` query heads
on Hkv ``kv_heads`` KV heads of D ``head_dim``, W ``sliding_window_size``,
eps ``rms_norm_eps``; RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * scale; no
bias anywhere). Layer i is sliding where ``sliding_window_layout[i]`` is 1
and carries position where ``rope_layout[i]`` is 1; the published period is
[full, sliding, sliding, sliding] with position on the sliding layers alone.
What ``config.json`` has no key for is in the configuration file's
``assumed``, each item with its ground:

* z = x W_r  -> [T, E]: the router's logits from the block's input, NO norm
  before it; S = the ``top_k`` largest of z a token; g = softmax(z[S])
  (``moe_primary_router_apply_softmax``, ``norm_topk_prob``), which is the
  softmax over all E, its top ``top_k`` renormalised. No auxiliary loss,
  nothing dropped.
* u = RMSNorm_1(x); q = u W_q -> [T, H, D], k = u W_k, v = u W_v ->
  [T, Hkv, D]; NO norm on q or k, no gate.
* layer with position: q, k rotated over all D dims, halves rotated, theta
  ``rope_theta``. Layer without: nothing (NoPE).
* Query head j reads KV head j // (H / Hkv); scores q.k * D^-0.5; key s is
  visible to query t iff s <= t and, on a sliding layer, t - s < W (the
  query's own token is one of the W); softmax in float32;
  h = x + (P v) W_o.
* m = RMSNorm_2(h); y = h + sum_{e in S, e held here} g_e *
  (relu(m W1_e) * (m W3_e)) W2_e. This chip holds experts ``expert_first ..
  expert_first + experts_held``: the sum runs over the held experts a token
  chose and what the absent ones would add is left out (guide
  model-configs, section 4), while S and the softmax are over all E.
  Computed as a plain loop over the held experts: each one on every token,
  times a gate that is zero where the token did not choose it.
* Out: RMSNorm, untied head [vocab, d], mean next-token cross entropy over
  the (sliced) vocabulary.
* Weights: normal(``init_std``) for every matrix and the embedding, norm
  scales 1.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``, no
kernel, no walk. It imports nothing of the program and is handed nothing the
program made: weights and batches are made again from the seed. What keeps
it inside a chip's memory at T = 16,384 and changes no arithmetic: the T x T
scores are taken ``q_block`` queries at a time with the window as a mask,
every block is recomputed in the backward pass (``jax.checkpoint``), the
held experts are walked one at a time.

``precision``: ``"float32"`` is the reference proper; ``"float8"`` is the
CONTROL (operands of every matmul, router included, rounded to
``float8_e4m3fn``), the nearest precision below the bfloat16 the
configuration states. Its cotangents are rounded to e4m3 too (the transpose
of the casts), which has no infinity and turns what passes 448 into NaN,
and under the SUM of 16,384 tokens' losses some pass it: the control
differentiates the loss under the loss scaling a float8 run trains with
(``finite_under_scale``) and stays finite.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.lib.reference_gpt2 import (PRECISIONS, _is_spec, _mm,
                                           leaf_norms, path_dict)
# The same plain pieces as the other mixture references: RMSNorm and the
# rotary embedding over the whole head (halves rotated).
from benchmarks.lib.reference_sparse_moe import rms_norm, rope

__all__ = ["PRECISIONS", "sizes_from_config", "param_shapes", "make_params",
           "loss_sum", "finite_under_scale", "train_steps", "leaf_norms", "path_dict", "attention",
           "route", "experts_share", "block", "logits"]


def sizes_from_config(cfg: dict) -> dict:
    """The sizes this file needs, from the published key names. ``layers``
    is the depth as run (``num_hidden_layers`` stays the published 52) and
    the two layouts their first ``layers`` entries, as tuples of bools."""
    layers = cfg["layers"]
    sliding, roped = (tuple(bool(v) for v in cfg[key][:layers])
                      for key in ("sliding_window_layout", "rope_layout"))
    if len(sliding) != layers or len(roped) != layers:
        raise ValueError(f"layouts {sliding}, {roped} for {layers} layers")
    if not (cfg["moe_primary_router_apply_softmax"]
            and cfg["norm_topk_prob"]):
        raise ValueError("only the softmax over the chosen logits is "
                         "written here")
    experts = cfg["moe_num_primary_experts"]
    return dict(
        layers=layers, sliding=sliding, roped=roped,
        d_model=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        vocab=cfg["vocab_size"], window=cfg["sliding_window_size"],
        experts=experts, experts_held=cfg.get("num_local_experts", experts),
        expert_first=cfg.get("first_local_expert", 0),
        top_k=cfg["moe_num_active_primary_experts"],
        d_expert=cfg["moe_ffn_hidden_size"], eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        init_std=cfg.get("initializer_range", 0.02))


def param_shapes(s: dict) -> dict:
    """The parameter tree (name -> (shape, init)): normal(init_std) for
    every matrix and the embedding, ones for every RMSNorm scale; no bias,
    no q / k norm, no gate, no shared expert."""
    d, D, std = s["d_model"], s["head_dim"], s["init_std"]
    H, Hk, f, held = s["heads"], s["kv_heads"], s["d_expert"], \
        s["experts_held"]

    def w(*shape):
        return (shape, ("normal", std))

    def ones(n):
        return {"scale": ((n,), ("ones",))}

    tree = {"embed": w(s["vocab"], d), "head": w(s["vocab"], d),
            "ln_f": ones(d)}
    for i in range(s["layers"]):
        tree[f"h{i}"] = {
            "ln1": ones(d), "ln2": ones(d),
            "attn": {"wq": w(d, H * D), "wk": w(d, Hk * D),
                     "wv": w(d, Hk * D), "wo": w(H * D, d)},
            "moe": {"router": w(d, s["experts"]), "w1": w(held, d, f),
                    "w3": w(held, d, f), "w2": w(held, f, d)}}
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


# -- the pieces of a block ----------------------------------------------------

def attention(q, k, v, window, mm, q_block: int):
    """o [T, H, D]: softmax attention of query t over keys s <= t, and
    t - s < ``window`` where it is not None, as explicit softmax maps
    ``q_block`` queries at a time."""
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


def route(x, router, top_k: int, mm):
    """(experts [T, K], gates [T, K]): the ``top_k`` largest logits of
    x W_r and the softmax over those ``top_k`` alone, the published way."""
    top, experts = jax.lax.top_k(mm("tc,ce->te", x, router), top_k)
    return experts, jax.nn.softmax(top, axis=-1)


def experts_share(m, p, experts, gates, first: int, mm):
    """What the held experts ``first .. first + len(w1)`` add for tokens m
    [T, d] routed as (experts, gates): a plain loop, each held expert on
    every token under the mask of the tokens that chose it."""
    held = p["w1"].shape[0]

    @jax.checkpoint
    def one(y, args):
        e, w1, w3, w2 = args
        gate = jnp.where(experts == e, gates, 0.0).sum(-1)        # [T]
        h = jax.nn.relu(mm("tc,cf->tf", m, w1)) * mm("tc,cf->tf", m, w3)
        return y + gate[:, None] * mm("tf,fc->tc", h, w2), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        first + jnp.arange(held), p["w1"], p["w3"], p["w2"]))
    return y


def block(x, p, i: int, s: dict, mm, q_block: int):
    """Layer ``i`` on x [T, d]."""
    T, _ = x.shape
    H, Hk, D = s["heads"], s["kv_heads"], s["head_dim"]
    experts, gates = route(x, p["moe"]["router"], s["top_k"], mm)
    u = rms_norm(x, p["ln1"]["scale"], s["eps"])
    a = p["attn"]
    q = mm("tc,cf->tf", u, a["wq"]).reshape(T, H, D)
    k = mm("tc,cf->tf", u, a["wk"]).reshape(T, Hk, D)
    v = mm("tc,cf->tf", u, a["wv"]).reshape(T, Hk, D)
    if s["roped"][i]:
        q, k = rope(q, s["rope_theta"]), rope(k, s["rope_theta"])
    o = attention(q, k, v, s["window"] if s["sliding"][i] else None, mm,
                  q_block)
    h = x + mm("tf,fc->tc", o.reshape(T, H * D), a["wo"])
    m = rms_norm(h, p["ln2"]["scale"], s["eps"])
    return h + experts_share(m, p["moe"], experts, gates, s["expert_first"],
                             mm)


def logits(params, toks, s: dict, precision: str = "float32",
           q_block: int = 256):
    """[T, vocab] of one row of token ids ``toks`` [T]."""
    mm = _mm(precision)
    x = params["embed"][toks]
    for i in range(s["layers"]):
        x = block(x, params[f"h{i}"], i, s, mm, q_block)
    x = rms_norm(x, params["ln_f"]["scale"], s["eps"])
    return mm("tc,vc->tv", x, params["head"])


def loss_sum(params, tokens, s: dict, precision: str = "float32",
             q_block: int = 256):
    """Summed next-token cross entropy over ``tokens`` [rows, T + 1]."""
    mm = _mm(precision)

    @jax.checkpoint
    def head(x, y_ids):
        x = rms_norm(x, params["ln_f"]["scale"], s["eps"])
        logp = jax.nn.log_softmax(mm("tc,vc->tv", x, params["head"]), -1)
        return -jnp.take_along_axis(logp, y_ids[:, None], axis=-1).sum()

    def row(total, toks):
        x = params["embed"][toks[:-1]]
        for i in range(s["layers"]):
            x = jax.checkpoint(functools.partial(
                block, i=i, s=s, mm=mm, q_block=q_block))(x, params[f"h{i}"])
        return total + head(x, toks[1:]), None

    total, _ = jax.lax.scan(row, jnp.float32(0), tokens)
    return total


def finite_under_scale(run, scale: float = 1.0, least: float = 2.0 ** -24):
    """(``run(scale)``, scale) at the largest ``scale``, halved from the
    one handed in and not below ``least``, whose result is finite in every
    loss and leaf norm: dynamic loss scaling as a float8 run trains with,
    steps that overflowed made again under half the scale. ``run`` returns
    what ``train_steps`` returns, as host values."""
    import math

    while True:
        out = run(scale)
        if scale <= least or all(math.isfinite(float(v)) for v in (
                *out["loss"], *out["grad_norm"].values(),
                *out.get("delta_norm", {}).values())):
            return out, scale
        scale /= 2


def warmup_schedule(opt: dict):
    """step (0 for the first) -> learning rate: ``opt["lr"]`` reached
    linearly over ``opt["warmup_steps"]`` steps and held; the first step
    trains at ``lr / warmup_steps``, not at 0. The builder hands this to
    ``optax.adamw`` and ``train_steps`` below reads it, so the program and
    its reference cannot warm up differently."""
    return lambda step: opt["lr"] * jnp.minimum(
        1.0, (step + 1) / opt["warmup_steps"])


def train_steps(seed, batches, s: dict, opt: dict, micro_rows: int,
                precision: str = "float32", q_block: int = 256,
                loss_scale=None):
    """Follow the first ``len(batches)`` steps of training from ``seed``:
    AdamW behind a clip by the global norm and under ``warmup_schedule``,
    written out in full.
    ``batches`` is [steps, rows, T + 1]; a step's gradient is the mean over
    all its rows' tokens (``micro_rows`` is taken as the whole batch: rows
    are walked one at a time inside ``loss_sum``). ``loss_scale`` (a power
    of two, the float8 control's: ``finite_under_scale``) multiplies the
    summed loss before it is differentiated and divides loss and gradient
    again. Returns what lib/reference_gpt2.py ``train_steps`` returns."""
    steps, rows, width = batches.shape
    del micro_rows
    n_tok = rows * (width - 1)

    def loss_fn(p, tokens):
        total = loss_sum(p, tokens, s=s, precision=precision,
                         q_block=q_block)
        return total if loss_scale is None else loss_scale * total

    if loss_scale is not None:
        n_tok = n_tok * loss_scale
    grad_fn = jax.value_and_grad(loss_fn)
    rate = warmup_schedule(opt)

    def one_step(carry, tokens):
        p, m, v, t = carry
        lr = rate(t)
        loss, g = grad_fn(p, tokens)
        loss, g = loss / n_tok, jax.tree.map(lambda a: a / n_tok, g)
        norms = leaf_norms(g)
        gnorm = jnp.sqrt(sum(n ** 2 for n in norms.values()))
        clip = jnp.where(gnorm < opt["clip_norm"], 1.0,
                         opt["clip_norm"] / gnorm)
        t = t + 1
        c1, c2 = 1 - opt["b1"] ** t, 1 - opt["b2"] ** t
        m = jax.tree.map(
            lambda a, b: opt["b1"] * a + (1 - opt["b1"]) * clip * b, m, g)
        v = jax.tree.map(
            lambda a, b: opt["b2"] * a + (1 - opt["b2"]) * (clip * b) ** 2,
            v, g)
        p = jax.tree.map(
            lambda w, a, b: w - lr * (
                (a / c1) / (jnp.sqrt(b / c2) + opt["eps"])
                + opt["weight_decay"] * w), p, m, v)
        return (p, m, v, t), (loss, norms)

    p0 = make_params(seed, s)
    zeros = jax.tree.map(jnp.zeros_like, p0)
    (p, _, _, _), (losses, norms) = jax.lax.scan(
        one_step, (p0, zeros, zeros, jnp.float32(0)), batches)
    delta = leaf_norms(jax.tree.map(jnp.subtract, p, make_params(seed, s)))
    return {"loss": losses,
            "grad_norm": jax.tree.map(lambda a: a[0], norms),
            "delta_norm": delta}
