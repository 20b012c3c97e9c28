"""Plain reference for a decoder with grouped-KV attention behind a learned
sparse indexer and a top-k mixture of gated experts: forward, loss,
gradient and AdamW.

The block, on x in R^{T x d} (``config.json`` of Keye-VL-2.0-30B-A3B's
language model; what the config is silent on follows the Qwen3-MoE block
whose sizes these are, and DeepSeek-V3.2-Exp's published sparse attention;
each such choice is in the configuration file's ``assumed``):

    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h))

* Attention, u = RMSNorm(x): q = W_q u (``heads`` x D), k = W_k u,
  v = W_v u (``kv_heads`` x D, each shared by heads/kv_heads query heads);
  RMSNorm with a learned scale over the D dims of every q and k head;
  rotary embedding over all D dims (halves rotated, theta ``rope_theta``);
  scores q.k / sqrt(D).
* Indexer, on stop_gradient(u): qI = W_qI u (``idx_heads`` x ``idx_dim``),
  kI = W_kI u (one head), w = W_w u; the rotary embedding on qI and kI;
  I[t, s] = (idx_heads * idx_dim)^-1/2 * sum_j w[t, j] relu(qI[t, j].kI[s])
  for s <= t. The selected set of query t is
  S_t = {s <= t : I[t, s] >= the topk-th largest of I[t, 0..t]} (all of
  0..t while t < topk). DEPARTURE from a strict top-k: entries that tie
  with the topk-th largest are all kept. No gradient passes through the
  selection, so the indexer's weights have a zero gradient.
* Output: o[t, h] = sum_{s in S_t} softmax_{S_t}(q.k / sqrt(D)) v, then W_o.
* MoE, z = RMSNorm(h): p = softmax(W_r z) over ``experts``; the ``top_k``
  largest, renormalised to sum 1; expert e is W2_e(silu(W1_e z) * W3_e z).
  This chip holds experts ``expert_first .. expert_first + experts_held``:
  the sum runs over the held experts a token chose; what the absent
  experts would add is left out (guide model-configs, section 4). Nothing
  is dropped. Computed densely: every held expert on every token, times a
  gate that is zero where the token did not choose it.
* Final RMSNorm, then an untied head [vocab, d]; mean next-token cross
  entropy over the (sliced) vocabulary.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``,
no kernel. It imports nothing of the program and is handed nothing the
program made: weights and batches are made again from the seed. What keeps
it inside a chip's memory at T = 16,384 and changes no arithmetic: the
T x T scores are taken ``q_block`` queries at a time, every block and
layer is recomputed in the backward pass (``jax.checkpoint``), the per-row
threshold is kept from the forward pass (a sort a row is the slowest thing
here), and the held experts are walked one at a time.

``precision``: ``"float32"`` is the reference proper; ``"float8"`` is the
CONTROL (operands of every matmul, index scores and router included,
rounded to ``float8_e4m3fn``), the nearest precision below the bfloat16 the
configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from benchmarks.lib.reference_gpt2 import (PRECISIONS, _is_spec, _mm,
                                           leaf_norms, path_dict)

__all__ = ["PRECISIONS", "sizes_from_config", "param_shapes", "make_params",
           "loss_sum", "train_steps", "leaf_norms", "path_dict",
           "index_scores", "selection", "attention", "moe", "moe_share"]


def sizes_from_config(cfg: dict) -> dict:
    """The sizes this file needs, from the published key names. ``layers``
    is the depth as run (``num_hidden_layers`` stays the published 48)."""
    sa = cfg["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the indexer is written for one key head")
    return dict(
        layers=cfg["layers"], d_model=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], vocab=cfg["vocab_size"],
        experts=cfg["num_experts"], experts_held=cfg["num_local_experts"],
        expert_first=cfg.get("first_local_expert", 0),
        top_k=cfg["num_experts_per_tok"], d_expert=cfg["moe_intermediate_size"],
        eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        idx_heads=sa["indexer_num_heads"], idx_dim=sa["indexer_head_dim"],
        idx_topk=sa["topk"], init_std=cfg.get("initializer_range", 0.02))


def param_shapes(s: dict) -> dict:
    """The parameter tree (name -> (shape, init)): normal(init_std) for
    every matrix and the embedding, ones for every RMSNorm scale (the
    family's convention); no bias anywhere."""
    d, D, std = s["d_model"], s["head_dim"], s["init_std"]
    H, Hk, f = s["heads"], s["kv_heads"], s["d_expert"]
    El = s["experts_held"]

    def w(*shape):
        return (shape, ("normal", std))

    def ones(n):
        return ((n,), ("ones",))

    tree = {"embed": w(s["vocab"], d), "head": w(s["vocab"], d),
            "ln_f": {"scale": ones(d)}}
    for i in range(s["layers"]):
        tree[f"h{i}"] = {
            "ln1": {"scale": ones(d)},
            "attn": {"wq": w(d, H * D), "wk": w(d, Hk * D),
                     "wv": w(d, Hk * D), "wo": w(H * D, d),
                     "q_norm": ones(D), "k_norm": ones(D)},
            "indexer": {"wq": w(d, s["idx_heads"] * s["idx_dim"]),
                        "wk": w(d, s["idx_dim"]), "ww": w(d, s["idx_heads"])},
            "ln2": {"scale": ones(d)},
            "moe": {"router": w(d, s["experts"]), "w1": w(El, d, f),
                    "w3": w(El, d, f), "w2": w(El, f, d)},
        }
    return tree


def make_params(seed, s: dict):
    """float32 weights from ``seed`` (a uint32 array or an int): every
    leaf its own draw of standard normals, in its own shape, keyed by its
    position in the flattened tree. (lib/reference_gpt2.py cuts one draw
    into all the leaves; at 659M weights the TPU compiler lays that draw
    out once a leaf, [n / last dim, last dim], and a [n/16, 16] copy alone
    pads to 21 GB.) Jit it: every leaf is made on the device."""
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

def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rope(x, theta: float):
    """Rotary embedding over the last dim of x [T, heads, D], positions
    0..T-1, halves rotated: (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin)
    with x1 = x[..., :D/2]. For text tokens the three ``mrope_section``
    position ids coincide, so this is the whole of the model's mrope."""
    T, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]   # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def index_scores(qi, ki, w, mm):
    """I [q, T] of queries qi [q, Hi, Di], w [q, Hi] against keys ki
    [T, Di]; the causal cut is the caller's."""
    Hi, Di = qi.shape[1], qi.shape[2]
    dots = jax.nn.relu(mm("qhd,kd->qhk", qi, ki))
    return (Hi * Di) ** -0.5 * jnp.einsum("qh,qhk->qk", w, dots)


def _blocks(T: int, q_block: int) -> int:
    q_block = min(q_block, T)
    if T % q_block:
        raise ValueError(f"q_block {q_block} does not divide T {T}")
    return q_block


def selection(qi, ki, w, topk: int, mm, q_block: int):
    """tau [T]: the ``topk``-th largest of I[t, 0..t] (-inf while
    t < topk: every causal key is then selected). Exact, by
    ``lax.top_k`` a row."""
    T = ki.shape[0]
    bq = _blocks(T, q_block)
    k = min(topk, T)

    def one(args):
        qb, wb, t = args
        I = index_scores(qb, ki, w=wb, mm=mm)
        I = jnp.where(jnp.arange(T)[None, :] <= t[:, None], I, -jnp.inf)
        return jax.lax.top_k(I, k)[0][:, -1]

    tau = jax.lax.map(one, (qi.reshape(T // bq, bq, *qi.shape[1:]),
                            w.reshape(T // bq, bq, -1),
                            jnp.arange(T).reshape(T // bq, bq)))
    return tau.reshape(T)


def attention(q, k, v, qi, ki, w, tau, mm, q_block: int, selected=None):
    """o [T, H, D]: softmax attention of every query over its selected set
    S_t = {s <= t : I[t, s] >= tau[t]} (or ``selected`` [T, T] bool where
    given), ``q_block`` queries at a time."""
    T, H, D = q.shape
    Hk = k.shape[1]
    bq = _blocks(T, q_block)

    @jax.checkpoint
    def one(args):
        qb, qib, wb, taub, t, sel = args
        if selected is None:
            sel = index_scores(qib, ki, w=wb, mm=mm) >= taub[:, None]
        sel = sel & (jnp.arange(T)[None, :] <= t[:, None])
        qg = qb.reshape(bq, Hk, H // Hk, D)
        scores = mm("qkgd,skd->kgqs", qg, k) * D ** -0.5
        probs = jax.nn.softmax(
            jnp.where(sel[None, None], scores, -jnp.inf), axis=-1)
        return mm("kgqs,skd->qkgd", probs, v).reshape(bq, H, D)

    n = T // bq
    sel = (jnp.zeros((n, bq, 1), bool) if selected is None
           else selected.reshape(n, bq, T))
    o = jax.lax.map(one, (
        q.reshape(n, bq, H, D), qi.reshape(n, bq, *qi.shape[1:]),
        w.reshape(n, bq, -1), tau.reshape(n, bq),
        jnp.arange(T).reshape(n, bq), sel))
    return o.reshape(T, H, D)


def route(z, router, top_k: int, mm):
    """(experts [T, K], gates [T, K]): the ``top_k`` largest of
    softmax(z W_r), renormalised to sum 1 (``norm_topk_prob``)."""
    probs = jax.nn.softmax(mm("tc,ce->te", z, router), axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)
    return experts, gates / gates.sum(-1, keepdims=True)


def moe_share(z, p, experts, gates, first: int, mm):
    """What the held experts ``first .. first + len(w1)`` add for tokens z
    [T, d] routed as (experts, gates). Dense: each held expert on every
    token, times the token's gate for it (zero where not chosen)."""
    held = p["w1"].shape[0]

    @jax.checkpoint
    def one(y, args):
        e, w1, w3, w2 = args
        gate = jnp.where(experts == e, gates, 0.0).sum(-1)        # [T]
        h = jax.nn.silu(mm("tc,cf->tf", z, w1)) * mm("tc,cf->tf", z, w3)
        return y + gate[:, None] * mm("tf,fc->tc", h, w2), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(z), (
        first + jnp.arange(held), p["w1"], p["w3"], p["w2"]))
    return y


def moe(z, p, s: dict, mm):
    experts, gates = route(z, p["router"], s["top_k"], mm)
    return moe_share(z, p, experts, gates, s["expert_first"], mm)


def _block(x, p, s: dict, mm, q_block: int):
    """One layer on x [T, d]."""
    T, d = x.shape
    H, Hk, D = s["heads"], s["kv_heads"], s["head_dim"]
    u = rms_norm(x, p["ln1"]["scale"], s["eps"])
    a = p["attn"]
    q = rms_norm(mm("tc,cf->tf", u, a["wq"]).reshape(T, H, D), a["q_norm"],
                 s["eps"])
    k = rms_norm(mm("tc,cf->tf", u, a["wk"]).reshape(T, Hk, D), a["k_norm"],
                 s["eps"])
    v = mm("tc,cf->tf", u, a["wv"]).reshape(T, Hk, D)
    q, k = rope(q, s["rope_theta"]), rope(k, s["rope_theta"])
    ui, ix = jax.lax.stop_gradient(u), p["indexer"]
    qi = rope(mm("tc,cf->tf", ui, ix["wq"]).reshape(
        T, s["idx_heads"], s["idx_dim"]), s["rope_theta"])
    ki = rope(mm("tc,cf->tf", ui, ix["wk"])[:, None, :], s["rope_theta"])[:, 0]
    w = mm("tc,ch->th", ui, ix["ww"])
    qi, ki, w = (jax.lax.stop_gradient(t) for t in (qi, ki, w))
    tau = checkpoint_name(selection(qi, ki, w, s["idx_topk"], mm, q_block),
                          "tau")
    o = attention(q, k, v, qi, ki, w, tau, mm, q_block)
    h = x + mm("tf,fc->tc", o.reshape(T, H * D), a["wo"])
    z = rms_norm(h, p["ln2"]["scale"], s["eps"])
    return h + moe(z, p["moe"], s, mm)


def loss_sum(params, tokens, s: dict, precision: str = "float32",
             q_block: int = 256):
    """Summed next-token cross entropy over ``tokens`` [rows, T + 1]."""
    mm = _mm(precision)
    block = jax.checkpoint(
        functools.partial(_block, s=s, mm=mm, q_block=q_block),
        policy=jax.checkpoint_policies.save_only_these_names("tau"))
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves),
                           *(params[f"h{i}"] for i in range(s["layers"])))

    @jax.checkpoint
    def head(x, y_ids):
        x = rms_norm(x, params["ln_f"]["scale"], s["eps"])
        logp = jax.nn.log_softmax(mm("tc,vc->tv", x, params["head"]), -1)
        return -jnp.take_along_axis(logp, y_ids[:, None], axis=-1).sum()

    def row(total, toks):
        x = params["embed"][toks[:-1]]
        x, _ = jax.lax.scan(lambda h, p: (block(h, p), None), x, stacked)
        return total + head(x, toks[1:]), None

    total, _ = jax.lax.scan(row, jnp.float32(0), tokens)
    return total


def train_steps(seed, batches, s: dict, opt: dict, micro_rows: int,
                precision: str = "float32", q_block: int = 256):
    """Follow the first ``len(batches)`` steps of training from ``seed``:
    AdamW behind a clip by the global norm, written out in full.
    ``batches`` is [steps, rows, T + 1]; a step's gradient is the mean over
    all its rows' tokens (``micro_rows`` is taken as the whole batch: rows
    are walked one at a time inside ``loss_sum``). Returns what
    lib/reference_gpt2.py ``train_steps`` returns."""
    steps, rows, width = batches.shape
    del micro_rows
    n_tok = rows * (width - 1)
    grad_fn = jax.value_and_grad(functools.partial(
        loss_sum, s=s, precision=precision, q_block=q_block))

    def one_step(carry, tokens):
        p, m, v, t = carry
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
            lambda w, a, b: w - opt["lr"] * (
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
