"""Plain reference for the decoder-hybrid-decoder family (``model_type``
``phi4flash``; its paper, arXiv 2507.06607, names the architecture SambaY):
state-space layers, differential attention under a window or over every
key, gated memory units and cross-attention that read what earlier layers
made: forward, loss, gradient, AdamW and clip.

The equations, on x in R^{T x d} (d ``hidden_size``; H ``heads`` query heads
on Hkv ``kv_heads`` KV heads of D = d / H; Dn ``d_inner``; N ``d_state``;
W ``sliding_window``; LN(x) = (x - mean) * rsqrt(var + eps) * scale + bias).
``l`` is a layer's PUBLISHED index (``layers``), its kind ``layer_types``:

* Embedding x0 = E[tokens]; no positional embedding of any kind.
* Every layer: u = LN1(x); h = x + Mixer(u); z = LN2(h);
  y = h + W2 (silu(W1 z) * W3 z), no bias (the published ``gate_up_proj``
  [d, 2 * d_ff] is W1 and W3 side by side).
* ``mamba``: [xc | z'] = u W_in; xs = silu(conv(xc) + b_c), a depthwise
  causal convolution of ``d_conv`` taps (the last tap on the token itself);
  [dt | B | C] = xs W_x (``dt_rank`` + N + N); D_t = softplus(dt W_dt +
  b_dt); A = -exp(A_log); h_t = exp(D_t A) * h_{t-1} + (D_t * xs_t) (x) B_t,
  h_{-1} = 0; m_t = h_t . C_t + Dskip * xs_t; out = (m * silu(z')) W_out.
  The last such layer before a ``gmu`` hands on m.
* ``sliding_attention`` / ``full_attention``: [q | k | v] = u W_qkv. Heads
  pair up, (2p, 2p + 1): q1, q2 of query pair p; k1, k2, v1, v2 of KV pair
  p // (H / Hkv). P1 = softmax(q1 k1^T D^-0.5 + mask), P2 likewise of q2,
  k2; a = P1 [v1 | v2] - lam * P2 [v1 | v2] (2D wide);
  lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,
  lam0 = 0.8 - 0.6 exp(-0.3 l); o = a * rsqrt(mean(a^2) + eps) * subln *
  (1 - lam0); out = concat_p(o) W_out. Key s is visible to query t iff
  0 <= t - s, and t - s < W on a sliding layer. The last full layer before
  a ``cross_attention`` hands on its k and v.
* ``cross_attention``: q = u W_q only, k and v handed on; the same form with
  its own lam and subln; causal, no window.
* ``gmu``: out = (silu(u W_in) * m) W_out.
* Out: LN, the head is E^T, mean next-token cross entropy over the (sliced)
  vocabulary.
* Weights: normal(``init_std``) for matrices and the embedding, LN (1, 0),
  the convolution's taps and bias uniform(+-d_conv^-0.5) (the framework's
  own draw for a depthwise convolution, which the family's code leaves as it
  is: normal(``init_std``) taps would leave the recurrent part of m some
  1e-4 of its skip part), A_log = log(1..N), Dskip = 1, b_dt = the inverse
  softplus of exp(uniform(log 1e-3, log 1e-1)), lq* / lk* normal(0.1),
  subln 1.

Departures from the published description (each also in the configuration
file's ``assumed``): the state-space sizes, the differential form and the
initialisers are the family's modeling code's, not ``config.json``'s; the
memory handed on is the scan's output before its gate; dropout is 0.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``, no
kernel; the scan is a ``lax.scan`` over tokens, the attention two explicit
softmax maps. It imports nothing of the program and is handed nothing the
program made: weights and batches are made again from the seed. What keeps
it inside a chip's memory at T = 8,192 and changes no arithmetic: the
scores are taken ``q_block`` queries at a time, the token scan runs in
chunks of ``SCAN_CHUNK`` tokens and every chunk, query block, block and the
head is recomputed in the backward pass (``jax.checkpoint``).

``precision``: ``"float32"`` is the reference proper; ``"float8"`` is the
CONTROL (operands of every matmul rounded to ``float8_e4m3fn``), the nearest
precision below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.lib.reference_gpt2 import (PRECISIONS, _is_spec, _layer_norm,
                                           _mm, leaf_norms, path_dict)

__all__ = ["PRECISIONS", "KINDS", "sizes_from_config", "param_shapes",
           "make_params", "loss_sum", "train_steps", "leaf_norms",
           "path_dict", "diff_attention", "selective_scan", "mamba", "gmu",
           "causal_conv", "hands_on", "hidden"]

MAMBA, SLIDING, FULL = "mamba", "sliding_attention", "full_attention"
GMU, CROSS = "gmu", "cross_attention"
KINDS = (MAMBA, SLIDING, FULL, GMU, CROSS)
SCAN_CHUNK = 64


def sizes_from_config(cfg: dict) -> dict:
    """The sizes this file needs, from the published key names; ``layers``
    is the tuple of published indices built, ``layer_types`` their kinds."""
    layers, kinds = tuple(cfg["layers"]), tuple(cfg["layer_types"])
    if len(kinds) != len(layers) or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types {kinds} for layers {layers}")
    d = cfg["hidden_size"]
    return dict(
        layers=layers, layer_types=kinds, d_model=d,
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=d // cfg["num_attention_heads"], vocab=cfg["vocab_size"],
        window=cfg["sliding_window"], d_ff=cfg["intermediate_size"],
        d_inner=cfg.get("mamba_expand", 2) * d,
        d_state=cfg.get("mamba_d_state", 16),
        d_conv=cfg.get("mamba_d_conv", 4),
        dt_rank=cfg.get("mamba_dt_rank", math.ceil(d / 16)),
        eps=cfg["layer_norm_eps"], init_std=cfg.get("initializer_range", 0.02))


def hands_on(kinds: tuple, i: int) -> bool:
    """Layer ``i`` is the last of its kind before a reader of what that
    kind makes."""
    reader = {MAMBA: GMU, FULL: CROSS}.get(kinds[i])
    later = kinds[i + 1:]
    if reader is None or reader not in later:
        return False
    return kinds[i] not in later[:later.index(reader)]


def param_shapes(s: dict) -> dict:
    """The parameter tree (name -> (shape, init))."""
    d, D, std = s["d_model"], s["head_dim"], s["init_std"]
    H, Hk, Dn, N, R = (s["heads"], s["kv_heads"], s["d_inner"], s["d_state"],
                       s["dt_rank"])

    def w(*shape):
        return (shape, ("normal", std))

    def ln():
        return {"scale": ((d,), ("ones",)), "bias": ((d,), ("zeros",))}

    def conv(*shape):
        return (shape, ("uniform", s["d_conv"] ** -0.5))

    def lam():
        return {**{n: ((D,), ("normal", 0.1))
                   for n in ("lq1", "lk1", "lq2", "lk2")},
                "subln": ((2 * D,), ("ones",)), "wo": w(H * D, d)}

    mixers = {
        MAMBA: lambda: {
            "in_proj": w(d, 2 * Dn), "conv_w": conv(s["d_conv"], Dn),
            "conv_b": conv(Dn), "x_proj": w(Dn, R + 2 * N),
            "dt_proj": w(R, Dn), "dt_bias": ((Dn,), ("dt_bias",)),
            "A_log": ((Dn, N), ("a_log",)), "D": ((Dn,), ("ones",)),
            "out_proj": w(Dn, d)},
        SLIDING: lambda: {"wqkv": w(d, (H + 2 * Hk) * D), **lam()},
        FULL: lambda: {"wqkv": w(d, (H + 2 * Hk) * D), **lam()},
        CROSS: lambda: {"wq": w(d, H * D), **lam()},
        GMU: lambda: {"in_proj": w(d, Dn), "out_proj": w(Dn, d)},
    }
    tree = {"embed": w(s["vocab"], d), "ln_f": ln()}
    for i, kind in enumerate(s["layer_types"]):
        tree[f"h{i}"] = {
            "ln1": ln(), "ln2": ln(), "mixer": mixers[kind](),
            "mlp": {"w1": w(d, s["d_ff"]), "w3": w(d, s["d_ff"]),
                    "w2": w(s["d_ff"], d)}}
    return tree


def _draw(key, shape, init):
    kind = init[0]
    if kind == "normal":
        return init[1] * jax.random.normal(key, shape, jnp.float32)
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, -init[1], init[1])
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "a_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(init)


def make_params(seed, s: dict):
    """float32 weights from ``seed`` (a uint32 array or an int): every leaf
    its own draw, keyed by its position in the flattened tree. Jit it: every
    leaf is made on the device."""
    key = jax.random.key(jnp.asarray(seed, jnp.uint32))
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(s), is_leaf=_is_spec)
    return jax.tree_util.tree_unflatten(treedef, [
        _draw(jax.random.fold_in(key, i), shape, init)
        for i, (_, (shape, init)) in enumerate(flat)])


# -- the mixers ---------------------------------------------------------------

def causal_conv(x, w, b):
    """x [T, C], w [taps, C], b [C]: out_t = b + sum_k w[k] x[t - taps + 1
    + k]."""
    taps, T = w.shape[0], x.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return b + sum(w[k] * padded[k:k + T] for k in range(taps))


def selective_scan(xs, dt, A, Bm, Cm, Dskip):
    """m [T, Dn]: the recurrence token by token (``lax.scan``), in chunks
    of ``SCAN_CHUNK`` tokens that the backward pass recomputes."""
    T, Dn = xs.shape
    chunk = math.gcd(T, SCAN_CHUNK)

    def token(h, args):
        x_t, dt_t, b_t, c_t = args
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * x_t)[:, None] * b_t
        return h, h @ c_t + Dskip * x_t

    @jax.checkpoint
    def run(h, args):
        return jax.lax.scan(token, h, args)

    cut = [a.reshape(T // chunk, chunk, -1) for a in (xs, dt, Bm, Cm)]
    _, m = jax.lax.scan(run, jnp.zeros(A.shape, jnp.float32), tuple(cut))
    return m.reshape(T, Dn)


def mamba(u, p, s: dict, mm):
    """(the mixer's output [T, d], the scan's output m [T, Dn])."""
    R, N = s["dt_rank"], s["d_state"]
    xc, z = jnp.split(mm("tc,cf->tf", u, p["in_proj"]), 2, axis=-1)
    xs = jax.nn.silu(causal_conv(xc, p["conv_w"], p["conv_b"]))
    dt, Bm, Cm = jnp.split(mm("tf,fr->tr", xs, p["x_proj"]), (R, R + N),
                           axis=-1)
    dt = jax.nn.softplus(mm("tr,rf->tf", dt, p["dt_proj"]) + p["dt_bias"])
    m = selective_scan(xs, dt, -jnp.exp(p["A_log"]), Bm, Cm, p["D"])
    return mm("tf,fc->tc", m * jax.nn.silu(z), p["out_proj"]), m


def gmu(u, m, p, mm):
    return mm("tf,fc->tc", jax.nn.silu(mm("tc,cf->tf", u, p["in_proj"])) * m,
              p["out_proj"])


def diff_attention(q, k, v, p, l: int, window, s: dict, mm, q_block: int):
    """a [T, H / 2, 2D] normed: q [T, H, D], k and v [T, Hkv, D], the two
    softmax maps of every head pair written out, ``q_block`` queries at a
    time."""
    T, H, D = q.shape
    J = k.shape[1] // 2                     # KV pairs
    g = H // 2 // J                         # query pairs a KV pair
    bq = min(q_block, T)
    if T % bq:
        raise ValueError(f"q_block {bq} does not divide T {T}")
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * l)
    lam = jnp.exp(p["lq1"] @ p["lk1"]) - jnp.exp(p["lq2"] @ p["lk2"]) + lam0
    kp, vp = k.reshape(T, J, 2, D), v.reshape(T, J, 2, D)

    @jax.checkpoint
    def one(args):
        qb, t = args
        ahead = t[:, None] - jnp.arange(T)[None, :]              # t - s
        seen = ahead >= 0
        if window is not None:
            seen &= ahead < window
        qp = qb.reshape(bq, J, g, 2, D)
        scores = mm("qjged,sjed->jgeqs", qp, kp) * D ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        both = mm("jgeqs,sjfd->qjgefd", probs, vp).reshape(
            bq, J * g, 2, 2 * D)
        return both[:, :, 0] - lam * both[:, :, 1]

    n = T // bq
    a = jax.lax.map(one, (q.reshape(n, bq, H, D),
                          jnp.arange(T).reshape(n, bq))).reshape(
        T, H // 2, 2 * D)
    rms = jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + s["eps"])
    return a * rms * p["subln"] * (1.0 - lam0)


def _block(x, p, m, kv, i: int, s: dict, mm, q_block: int):
    """Built layer ``i`` on x [T, d]: (y, m, kv) with what it hands on."""
    T, d = x.shape
    H, Hk, D = s["heads"], s["kv_heads"], s["head_dim"]
    kind, mix = s["layer_types"][i], p["mixer"]
    u = _layer_norm(x, p["ln1"], s["eps"])
    if kind == MAMBA:
        out, made = mamba(u, mix, s, mm)
        if hands_on(s["layer_types"], i):
            m = made
    elif kind == GMU:
        out = gmu(u, m, mix, mm)
    else:
        if kind == CROSS:
            q, (k, v) = mm("tc,cf->tf", u, mix["wq"]), kv
        else:
            q, k, v = jnp.split(mm("tc,cf->tf", u, mix["wqkv"]),
                                (H * D, (H + Hk) * D), axis=-1)
            k, v = k.reshape(T, Hk, D), v.reshape(T, Hk, D)
            if kind == FULL and hands_on(s["layer_types"], i):
                kv = (k, v)
        a = diff_attention(q.reshape(T, H, D), k, v, mix, s["layers"][i],
                           s["window"] if kind == SLIDING else None, s, mm,
                           q_block)
        out = mm("tf,fc->tc", a.reshape(T, H * D), mix["wo"])
    h = x + out
    z = _layer_norm(h, p["ln2"], s["eps"])
    mlp = p["mlp"]
    y = h + mm("tf,fc->tc", jax.nn.silu(mm("tc,cf->tf", z, mlp["w1"]))
               * mm("tc,cf->tf", z, mlp["w3"]), mlp["w2"])
    return y, m, kv


def hidden(params, toks, s: dict, precision: str = "float32",
           q_block: int = 256):
    """The last layer's output [T, d] for token ids [T], before the final
    norm."""
    mm = _mm(precision)
    x, m, kv = params["embed"][toks], None, None
    for i in range(len(s["layers"])):
        x, m, kv = jax.checkpoint(functools.partial(
            _block, i=i, s=s, mm=mm, q_block=q_block))(
            x, params[f"h{i}"], m, kv)
    return x


def loss_sum(params, tokens, s: dict, precision: str = "float32",
             q_block: int = 256):
    """Summed next-token cross entropy over ``tokens`` [rows, T + 1]."""
    mm = _mm(precision)

    @jax.checkpoint
    def head(x, y_ids):
        x = _layer_norm(x, params["ln_f"], s["eps"])
        logp = jax.nn.log_softmax(mm("tc,vc->tv", x, params["embed"]), -1)
        return -jnp.take_along_axis(logp, y_ids[:, None], axis=-1).sum()

    def row(total, toks):
        x = hidden(params, toks[:-1], s, precision, q_block)
        return total + head(x, toks[1:]), None

    total, _ = jax.lax.scan(row, jnp.float32(0), tokens)
    return total


def train_steps(seed, batches, s: dict, opt: dict, micro_rows: int,
                precision: str = "float32", q_block: int = 256):
    """Follow the first ``len(batches)`` steps of training from ``seed``:
    AdamW behind a clip by the global norm, written out in full.
    ``batches`` is [steps, rows, T + 1]; a step's gradient is the mean over
    all its rows' tokens (rows are walked one at a time; ``micro_rows`` is
    taken as the whole batch). Returns what lib/reference_gpt2.py
    ``train_steps`` returns."""
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
            lambda a, b_: opt["b1"] * a + (1 - opt["b1"]) * clip * b_, m, g)
        v = jax.tree.map(
            lambda a, b_: opt["b2"] * a + (1 - opt["b2"]) * (clip * b_) ** 2,
            v, g)
        p = jax.tree.map(
            lambda w, a, b_: w - opt["lr"] * (
                (a / c1) / (jnp.sqrt(b_ / c2) + opt["eps"])
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
