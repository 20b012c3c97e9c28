"""Plain reference for the ``sdar_moe`` decoder (SDAR-30B-A3B-Chat's
``config.json``) trained under the block-diffusion objective of BD3-LM
(Arriola et al., ICLR 2025), which the SDAR report trains with: noise,
forward, loss, gradient and AdamW.

The equations, on one sequence ``x0 [L]`` of token ids, block length ``B``
(``L % B == 0``, ``n = L / B`` blocks), ``blk(i) = i // B``, mask id ``M``
(what ``config.json`` has no key for is in the configuration file's
``assumed``, each item with its ground):

1. **Noise**, from ``key = fold_in(key(seed), step)``, row ``r`` of the
   step's batch from ``split(fold_in(key, r))``: a block draws
   ``u_b ~ U[0, 1)`` (first half of the split) and
   ``t_b = eps + (1 - eps) u_b``, ``eps`` = 1e-3; a position draws
   ``v_i ~ U[0, 1)`` (second half); ``masked_i = v_i < t_blk(i)``;
   ``xt_i = M if masked_i else x0_i``.
2. **Rows**: tokens ``[xt ; x0]``, ``2 L`` rows, at positions
   ``[0 .. L-1 ; 0 .. L-1]`` for the rotary embedding. Every layer runs on
   all ``2 L`` rows: Qwen3-MoE's block,
   ``h = x + Attn(RMSNorm(x)); y = h + MoE(RMSNorm(h))``, RMSNorm with a
   learned scale over the D dims of every q and k head before the rotary
   embedding (all D dims, halves rotated, theta ``rope_theta``), query head
   j on KV head ``j // (H / Hkv)``, scores ``q.k / sqrt(D)``, softmax in
   float32, no bias anywhere; the router is ``softmax(z W_r)`` over
   ``experts``, the ``top_k`` largest renormalised to sum 1, expert e is
   ``W2_e(silu(W1_e z) * W3_e z)``; this chip holds experts
   ``expert_first .. expert_first + experts_held`` and what the absent ones
   would add is left out (guide model-configs, section 4).
3. **Mask**, query row ``r``, key row ``c``, a row's half noised
   (``< L``) or clean:
   * clean ``r`` sees clean ``c`` with ``blk(c) <= blk(r)``;
   * noised ``r`` sees clean ``c`` with ``blk(c) < blk(r)`` and noised
     ``c`` with ``blk(c) == blk(r)``;
   * nothing else. ``L^2 + L B`` pairs a query head.
4. **Loss**: final RMSNorm and the untied head ``[vocab, d]`` on the noised
   half only, no shift (the logits at position ``i`` predict ``x0_i``):
   ``(1 / L) sum_{i: masked_i} CE(logits_i, x0_i) / t_blk(i)``, the mean
   over the step's sequences. float32 throughout.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``,
no kernel. It imports nothing of the program and is handed nothing the
program made: weights, batches and noise are made again from the seed
(threefry gives the same draws on every backend). The attention is dense
under the mask as 3 defines it, row by row of the ``2 L x 2 L`` square, not
as the program cuts it. What keeps it inside a chip's memory at 16,384 rows
and changes no arithmetic: the scores are taken ``q_block`` queries at a
time, every block is recomputed in the backward pass (``jax.checkpoint``),
the held experts are walked one at a time.

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
# embedding over the whole head (halves rotated, positions 0 .. T-1), the
# softmax router and the held experts' dense share.
from benchmarks.lib.reference_sparse_moe import (moe_share, rms_norm, rope,
                                                 route)

__all__ = ["PRECISIONS", "sizes_from_config", "param_shapes", "make_params",
           "noise", "visible", "attention", "loss_sum", "train_steps",
           "leaf_norms", "path_dict"]

NOISE_EPS = 1e-3


def sizes_from_config(cfg: dict) -> dict:
    """The sizes this file needs, from the published key names; ``layers``
    is the depth as run, ``block_length`` the configuration's assumed one,
    ``mask_id`` the last row of the vocabulary slice."""
    return dict(
        layers=cfg["layers"], d_model=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], vocab=cfg["vocab_size"],
        experts=cfg["num_experts"], experts_held=cfg["num_local_experts"],
        expert_first=cfg.get("first_local_expert", 0),
        top_k=cfg["num_experts_per_tok"], d_expert=cfg["moe_intermediate_size"],
        eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        init_std=cfg.get("initializer_range", 0.02),
        block_length=cfg["block_length"], mask_id=cfg["vocab_size"] - 1)


def param_shapes(s: dict) -> dict:
    """The parameter tree (name -> (shape, init)): normal(init_std) for
    every matrix and the embedding, ones for every RMSNorm scale; no bias
    anywhere."""
    d, D, std = s["d_model"], s["head_dim"], s["init_std"]
    H, Hk, f, El = s["heads"], s["kv_heads"], s["d_expert"], s["experts_held"]

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
            "ln2": {"scale": ones(d)},
            "moe": {"router": w(d, s["experts"]), "w1": w(El, d, f),
                    "w3": w(El, d, f), "w2": w(El, f, d)},
        }
    return tree


def make_params(seed, s: dict):
    """float32 weights from ``seed`` (a uint32 array or an int): every leaf
    its own draw of standard normals, keyed by its position in the
    flattened tree (as lib/reference_sparse_moe.py makes its own). Jit it:
    every leaf is made on the device."""
    key = jax.random.key(jnp.asarray(seed, jnp.uint32))
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(s), is_leaf=_is_spec)
    leaves = [
        init[1] * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        if init[0] == "normal" else jnp.ones(shape, jnp.float32)
        for i, (_, (shape, init)) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


# -- the objective --------------------------------------------------------------

def noise(seed, step, tokens, s: dict):
    """Equation 1 on ``tokens [rows, L]``: (xt [rows, L], masked [rows, L]
    bool, t [rows, L])."""
    rows, L = tokens.shape
    B = s["block_length"]
    key = jax.random.fold_in(jax.random.key(jnp.asarray(seed, jnp.uint32)),
                             step)
    xt, masked, t = [], [], []
    for r in range(rows):
        ku, kv = jax.random.split(jax.random.fold_in(key, r))
        u = jax.random.uniform(ku, (L // B,), jnp.float32)
        v = jax.random.uniform(kv, (L,), jnp.float32)
        t.append(jnp.repeat(NOISE_EPS + (1.0 - NOISE_EPS) * u, B))
        masked.append(v < t[-1])
        xt.append(jnp.where(masked[-1], s["mask_id"], tokens[r]))
    return jnp.stack(xt), jnp.stack(masked), jnp.stack(t)


def visible(rows, L: int, B: int):
    """Equation 3: ``[len(rows), 2 L]`` bool, may query row r see key row
    c."""
    cols = jnp.arange(2 * L)
    q_clean, k_clean = (rows >= L)[:, None], (cols >= L)[None, :]
    q_blk, k_blk = ((rows % L) // B)[:, None], ((cols % L) // B)[None, :]
    return ((q_clean & k_clean & (k_blk <= q_blk))
            | (~q_clean & k_clean & (k_blk < q_blk))
            | (~q_clean & ~k_clean & (k_blk == q_blk)))


def attention(q, k, v, B: int, mm, q_block: int):
    """o [2L, H, D]: softmax attention of every row over the rows equation
    3 lets it see, ``q_block`` queries at a time."""
    T, H, D = q.shape
    Hk = k.shape[1]
    bq = min(q_block, T)
    if T % bq:
        raise ValueError(f"q_block {bq} does not divide the {T} rows")

    @jax.checkpoint
    def one(args):
        qb, rows = args
        seen = visible(rows, T // 2, B)
        qg = qb.reshape(bq, Hk, H // Hk, D)
        scores = mm("qkgd,skd->kgqs", qg, k) * D ** -0.5
        probs = jax.nn.softmax(
            jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return mm("kgqs,skd->qkgd", probs, v).reshape(bq, H, D)

    n = T // bq
    o = jax.lax.map(one, (q.reshape(n, bq, H, D),
                          jnp.arange(T).reshape(n, bq)))
    return o.reshape(T, H, D)


def _rope_halves(x, theta: float):
    """The rotary embedding at positions ``[0 .. L-1 ; 0 .. L-1]``."""
    L = x.shape[0] // 2
    return jnp.concatenate([rope(x[:L], theta), rope(x[L:], theta)])


def _block(x, p, s: dict, mm, q_block: int):
    """One layer on the rows x [2L, d]."""
    T, d = x.shape
    H, Hk, D = s["heads"], s["kv_heads"], s["head_dim"]
    u = rms_norm(x, p["ln1"]["scale"], s["eps"])
    a = p["attn"]
    q = rms_norm(mm("tc,cf->tf", u, a["wq"]).reshape(T, H, D), a["q_norm"],
                 s["eps"])
    k = rms_norm(mm("tc,cf->tf", u, a["wk"]).reshape(T, Hk, D), a["k_norm"],
                 s["eps"])
    v = mm("tc,cf->tf", u, a["wv"]).reshape(T, Hk, D)
    q, k = _rope_halves(q, s["rope_theta"]), _rope_halves(k, s["rope_theta"])
    o = attention(q, k, v, s["block_length"], mm, q_block)
    h = x + mm("tf,fc->tc", o.reshape(T, H * D), a["wo"])
    z = rms_norm(h, p["ln2"]["scale"], s["eps"])
    experts, gates = route(z, p["moe"]["router"], s["top_k"], mm)
    return h + moe_share(z, p["moe"], experts, gates, s["expert_first"], mm)


def loss_sum(params, xt, x0, masked, t, s: dict,
             precision: str = "float32", q_block: int = 256):
    """Equation 4 summed over the rows of the batch (``xt``, ``x0``,
    ``masked``, ``t`` all [rows, L])."""
    mm = _mm(precision)
    L = x0.shape[1]
    block = jax.checkpoint(
        functools.partial(_block, s=s, mm=mm, q_block=q_block))
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves),
                           *(params[f"h{i}"] for i in range(s["layers"])))

    @jax.checkpoint
    def head(x, ids, weight):
        x = rms_norm(x, params["ln_f"]["scale"], s["eps"])
        logp = jax.nn.log_softmax(mm("tc,vc->tv", x, params["head"]), -1)
        ce = -jnp.take_along_axis(logp, ids[:, None], axis=-1)[:, 0]
        return (ce * weight).sum() / L

    def row(total, args):
        noised, clean, m, level = args
        x = params["embed"][jnp.concatenate([noised, clean])]
        x, _ = jax.lax.scan(lambda h, p: (block(h, p), None), x, stacked)
        return total + head(x[:L], clean, jnp.where(m, 1.0 / level, 0.0)), \
            None

    total, _ = jax.lax.scan(row, jnp.float32(0), (xt, x0, masked, t))
    return total


def train_steps(seed, batches, s: dict, opt: dict, micro_rows: int,
                precision: str = "float32", q_block: int = 256):
    """Follow the first ``len(batches)`` steps of training from ``seed``:
    the noise of each step, AdamW behind a clip by the global norm, written
    out in full. ``batches`` is the job's [steps, rows, L + 1]: a row's
    first ``L`` ids are the sequence (the objective has no shift, so the
    job's last id is not used). A step's loss is the mean over its rows
    (rows are walked one at a time inside ``loss_sum``; ``micro_rows`` is
    taken as the whole batch). Returns what lib/reference_gpt2.py
    ``train_steps`` returns."""
    steps, rows, _ = batches.shape
    del micro_rows
    grad_fn = jax.value_and_grad(functools.partial(
        loss_sum, s=s, precision=precision, q_block=q_block))

    def one_step(carry, args):
        p, m, v, t = carry
        step, tokens = args
        x0 = tokens[:, :-1]
        xt, masked, level = noise(seed, step, x0, s)
        loss, g = grad_fn(p, xt, x0, masked, level)
        loss, g = loss / rows, jax.tree.map(lambda a: a / rows, g)
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
        one_step, (p0, zeros, zeros, jnp.float32(0)),
        (jnp.arange(steps), batches))
    delta = leaf_norms(jax.tree.map(jnp.subtract, p, make_params(seed, s)))
    return {"loss": losses,
            "grad_norm": jax.tree.map(lambda a: a[0], norms),
            "delta_norm": delta}
