"""Plain reference for the ``nemotron_h`` hybrid decoder (NVIDIA-Nemotron-3-
Super-120B-A12B's ``config.json``): one sublayer a layer by
``hybrid_override_pattern`` -- a Mamba-2 mixer, a grouped-KV attention layer
or sigmoid-routed squared-ReLU experts in a latent beside a full-width
shared expert, with the routers' selection bias carried as state: forward,
loss, gradient, AdamW and the bias update.

The equations, on x in R^{T x d} (d ``hidden_size``; RMSNorm(x) = x *
rsqrt(mean(x^2) + eps) * scale, eps ``layer_norm_epsilon``). Every layer is
``x <- x + F_l(u)``, ``u = RMSNorm_l(x)``; no bias but the convolution's.
``h``, ``G``, ``Hq``, ``Hkv`` are the Mamba heads, groups, query and KV
heads HELD by this chip (the configuration file's ``deployment``); what the
``config.json`` has no key for is in the file's ``assumed``:

* ``M`` (Mamba-2; P ``mamba_head_dim``, N ``ssm_state_size``):
  [z | xBC | dt] = u W_in, widths P h, P h + 2 N G, h;
  xBC = silu(conv1d_causal(xBC) + b_conv), depthwise, ``conv_kernel`` taps;
  [xs | B | C] = xBC, xs [T, h, P], B and C [T, G, N];
  D_t = softplus(dt_t + dt_bias) [T, h]; a_t = exp(-exp(A_log) * D_t), ONE
  scalar a head; S_t = a_t S_{t-1} + (D_t xs_t) (x) B_t with S [h, P, N],
  S_{-1} = 0, head i reading group i // (h / G); y_t = S_t C_t + Dskip xs_t;
  F = GroupRMSNorm(y * silu(z)) W_out: the gate first, then the norm over
  each group's P h / G channels with a learned weight. The recurrence is
  computed TOKEN BY TOKEN (``ssd_token_loop``), in runs of ``SCAN_CHUNK``
  tokens that the backward pass recomputes; ``ssd_chunked`` is the chunked
  form written out a second way, for the tests that hold the two together.
* ``*``: q = u W_q -> [T, Hq, D], k = u W_k, v = u W_v -> [T, Hkv, D]
  (D ``head_dim``); no position of any kind; query head j reads KV head
  j // (Hq / Hkv); scores q.k * D^-0.5; key s visible to query t iff
  s <= t; softmax in float32; F = (P v) W_o.
* ``E``: s = sigmoid(u W_r) in float32, W_r [d, E] over ALL E
  ``n_routed_experts``; S(u) = the ``num_experts_per_tok`` largest of s + b
  (b the layer's bias [E]: state, zero at the start); g_e =
  ``routed_scaling_factor`` * s_e / (sum_{j in S(u)} s_j + 1e-20)
  (``norm_topk_prob``): the bias chooses, it never weighs; l = u W_down
  [T, ``moe_latent_size``]; F = (sum_{e in S(u), e held} g_e relu(l W1_e)^2
  W2_e) W_up + relu(u Ws1)^2 Ws2. This chip holds experts ``expert_first ..
  expert_first + experts_held``: what the absent ones would add is left out
  (guide model-configs, section 4) while S(u), the gates' normaliser and
  the counts are over all E. Computed densely: every held expert on every
  token, times a gate that is zero where the token did not choose it.
* Bias update, once a step after the optimizer's, from the step's counts
  n [E] of token-choices per expert: delta = ``load_balance_coeff`` *
  sign(mean(n) - n); delta <- delta - mean(delta); b <- b + delta.
* Out: RMSNorm, untied head [vocab, d], mean next-token cross entropy over
  the (sliced) vocabulary.
* Weights: normal(``initializer_range``) for every matrix and the
  embedding, norm weights 1, the convolution's taps and bias
  uniform(+-conv_kernel^-0.5), A_log = log(uniform(1, 16)), Dskip = 1,
  dt_bias the inverse softplus of exp(uniform(log ``time_step_min``, log
  ``time_step_max``)) floored at ``time_step_floor``.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``, no
kernel. It imports nothing of the program and is handed nothing the program
made: weights and batches are made again from the seed. What keeps it inside
a chip's memory at T = 8,192 and changes no arithmetic: the T x T scores are
taken ``q_block`` queries at a time, every layer is recomputed in the
backward pass (``jax.checkpoint``), the held experts are walked one at a
time, the scan's states are kept at the ends of its runs only.

``precision``: ``"float32"`` is the reference proper; ``"float8"`` is the
CONTROL (operands of every matmul, the router's and the state's read
through C included, rounded to ``float8_e4m3fn``), the nearest precision
below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.lib.reference_afmoe import attention, bias_update
from benchmarks.lib.reference_gpt2 import (PRECISIONS, _is_spec, _mm,
                                           leaf_norms, path_dict)
from benchmarks.lib.reference_sambay import causal_conv
# The linear warm-up smallthinker-21b-a3b's cell trains under: the builder
# hands it to ``optax.adamw`` and ``train_steps`` reads it, so that the
# program and the reference cannot warm up differently.
from benchmarks.lib.reference_smallthinker import warmup_schedule
from benchmarks.lib.reference_sparse_moe import rms_norm

__all__ = ["PRECISIONS", "MAMBA", "ATTENTION", "EXPERTS", "sizes_from_config",
           "param_shapes", "make_params", "zero_biases", "loss_sum",
           "train_steps", "warmup_schedule", "leaf_norms", "path_dict",
           "ssd_token_loop", "ssd_chunked", "mamba", "attention_layer",
           "route", "experts", "shared_expert", "bias_update", "layer"]

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"
#: Tokens of a run of the token loop that the backward pass recomputes.
SCAN_CHUNK = 64


def sizes_from_config(cfg: dict) -> dict:
    """The sizes this file needs, from the published key names. ``kinds``
    are the letters of the layers built (``layers``: published indices
    into ``hybrid_override_pattern``)."""
    pattern = cfg["hybrid_override_pattern"]
    kinds = "".join(pattern[i] for i in cfg.get("layers",
                                                range(len(pattern))))
    if set(kinds) - {MAMBA, ATTENTION, EXPERTS}:
        raise ValueError(f"hybrid_override_pattern letters {kinds!r}")
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing is not written here")
    return dict(
        kinds=kinds, d_model=cfg["hidden_size"], vocab=cfg["vocab_size"],
        eps=cfg["layer_norm_epsilon"],
        mamba_heads=cfg["mamba_num_heads"], mamba_head_dim=cfg["mamba_head_dim"],
        groups=cfg["n_groups"], d_state=cfg["ssm_state_size"],
        taps=cfg["conv_kernel"], chunk=cfg["chunk_size"],
        dt_min=cfg["time_step_min"], dt_max=cfg["time_step_max"],
        dt_floor=cfg["time_step_floor"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        experts=cfg["n_routed_experts"],
        experts_held=cfg.get("num_local_experts", cfg["n_routed_experts"]),
        expert_first=cfg.get("first_local_expert", 0),
        top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"], latent=cfg["moe_latent_size"],
        d_shared=cfg["n_shared_experts"]
        * cfg["moe_shared_expert_intermediate_size"],
        route_norm=bool(cfg["norm_topk_prob"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        balance_coeff=float(cfg.get("load_balance_coeff", 0.0)),
        init_std=cfg.get("initializer_range", 0.02))


def d_inner(s: dict) -> int:
    return s["mamba_heads"] * s["mamba_head_dim"]


def conv_width(s: dict) -> int:
    return d_inner(s) + 2 * s["groups"] * s["d_state"]


def expert_layers(s: dict) -> list:
    return [f"h{i}" for i, k in enumerate(s["kinds"]) if k == EXPERTS]


def param_shapes(s: dict) -> dict:
    """The parameter tree (name -> (shape, init))."""
    d, std = s["d_model"], s["init_std"]
    h, Dn, Cw = s["mamba_heads"], d_inner(s), conv_width(s)
    H, Hk, D = s["heads"], s["kv_heads"], s["head_dim"]
    held, f, lat = s["experts_held"], s["d_expert"], s["latent"]

    def w(*shape):
        return (shape, ("normal", std))

    def ones(n):
        return ((n,), ("ones",))

    conv = ("uniform", s["taps"] ** -0.5)
    tree = {"embed": w(s["vocab"], d), "head": w(s["vocab"], d),
            "ln_f": {"scale": ones(d)}}
    for i, kind in enumerate(s["kinds"]):
        layer_ = {"norm": {"scale": ones(d)}}
        if kind == MAMBA:
            layer_["mixer"] = {
                "in_proj": w(d, Dn + Cw + h),
                "conv_w": ((s["taps"], Cw), conv), "conv_b": ((Cw,), conv),
                "dt_bias": ((h,), ("dt_bias", s["dt_min"], s["dt_max"],
                                   s["dt_floor"])),
                "A_log": ((h,), ("a_log",)), "D": ones(h),
                "norm": ones(Dn), "out_proj": w(Dn, d)}
        elif kind == ATTENTION:
            layer_["mixer"] = {"wq": w(d, H * D), "wk": w(d, Hk * D),
                               "wv": w(d, Hk * D), "wo": w(H * D, d)}
        else:
            layer_["moe"] = {
                "router": w(d, s["experts"]), "w_down": w(d, lat),
                "w_up": w(lat, d), "w1": w(held, lat, f),
                "w2": w(held, f, lat)}
            if s["d_shared"]:
                layer_["moe"].update(shared_w1=w(d, s["d_shared"]),
                                     shared_w2=w(s["d_shared"], d))
        tree[f"h{i}"] = layer_
    return tree


def _draw(key, shape, init):
    kind = init[0]
    if kind == "normal":
        return init[1] * jax.random.normal(key, shape, jnp.float32)
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, -init[1], init[1])
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if kind == "dt_bias":
        _, lo, hi, floor = init
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(lo), math.log(hi))), floor)
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


def zero_biases(s: dict) -> dict:
    """The routers' selection biases at the start, in the tree the program
    keeps them in: {layer: {"moe": {"bias": [E]}}}."""
    return {name: {"moe": {"bias": jnp.zeros((s["experts"],), jnp.float32)}}
            for name in expert_layers(s)}


# -- the Mamba-2 layer --------------------------------------------------------

def _by_head(a, h: int):
    """B or C [T, G, N] -> [T, h, N]: head i reads group i // (h / G)."""
    return jnp.repeat(a, h // a.shape[1], axis=1)


def ssd_token_loop(xs, dt, A_log, Bm, Cm, Dskip, mm):
    """y [T, h, P]: the recurrence token by token (``lax.scan``), in runs
    of ``SCAN_CHUNK`` tokens that the backward pass recomputes. xs
    [T, h, P]; dt [T, h]; A_log, Dskip [h]; Bm, Cm [T, G, N]."""
    T, h, P = xs.shape
    run_len = math.gcd(T, SCAN_CHUNK)
    Bh, Ch = _by_head(Bm, h), _by_head(Cm, h)
    decay = jnp.exp(-jnp.exp(A_log) * dt)                        # [T, h]

    def token(S, args):
        x_t, dt_t, a_t, b_t, c_t = args
        S = a_t[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return S, mm("hpn,hn->hp", S, c_t) + Dskip[:, None] * x_t

    @jax.checkpoint
    def run(S, args):
        return jax.lax.scan(token, S, args)

    cut = [a.reshape(T // run_len, run_len, *a.shape[1:])
           for a in (xs, dt, decay, Bh, Ch)]
    S0 = jnp.zeros((h, P, Bm.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(run, S0, tuple(cut))
    return y.reshape(T, h, P)


def ssd_chunked(xs, dt, A_log, Bm, Cm, Dskip, L: int):
    """The same y by chunks of ``L`` tokens (``L`` divides T), written out
    a second way and chunk after chunk: inside a chunk
    Y = ((C B^T) * Lm)(D * xs) + (C S_in) * exp(cs), with Lm[t, s] =
    prod_{s < r <= t} a_r for s <= t, and the state handed from chunk to
    chunk by S_out = exp(cs[L-1]) S_in + sum_t Lm[L-1, t] (D_t xs_t) (x)
    B_t. Float32 at ``highest``; the tests hold it and the program's op
    against the token loop."""
    T, h, P = xs.shape
    mm = _mm("float32")
    Bh, Ch = _by_head(Bm, h), _by_head(Cm, h)
    log_a = -jnp.exp(A_log) * dt                                 # [T, h]
    tri = jnp.tril(jnp.ones((L, L), bool))

    def chunk(S, args):
        x, d, la, b, c = args                     # [L, h, ..]
        cs = jnp.cumsum(la, axis=0)               # [L, h]
        lm = jnp.where(tri[:, :, None],
                       jnp.exp(jnp.where(tri[:, :, None],
                                         cs[:, None] - cs[None, :], 0.0)),
                       0.0)                       # [t, s, h]
        xd = x * d[:, :, None]
        y = mm("tsh,shp->thp", mm("thn,shn->tsh", c, b) * lm, xd) \
            + mm("thn,hpn->thp", c, S) * jnp.exp(cs)[:, :, None]
        S = jnp.exp(cs[-1])[:, None, None] * S + mm(
            "shp,shn->hpn", xd * lm[-1][:, :, None], b)
        return S, y + Dskip[:, None] * x

    cut = [a.reshape(T // L, L, *a.shape[1:])
           for a in (xs, dt, log_a, Bh, Ch)]
    S0 = jnp.zeros((h, P, Bm.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(chunk, S0, tuple(cut))
    return y.reshape(T, h, P)


def group_rms_norm(x, weight, groups: int, eps: float):
    g = x.reshape(x.shape[0], groups, -1)
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + eps)
    return g.reshape(x.shape) * weight


def mamba(u, p, s: dict, mm):
    T = u.shape[0]
    h, P, G, N = (s["mamba_heads"], s["mamba_head_dim"], s["groups"],
                  s["d_state"])
    Dn, Cw = d_inner(s), conv_width(s)
    z, xbc, dt = jnp.split(mm("tc,cf->tf", u, p["in_proj"]),
                           (Dn, Dn + Cw), axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xs, Bm, Cm = jnp.split(xbc, (Dn, Dn + G * N), axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd_token_loop(xs.reshape(T, h, P), dt, p["A_log"],
                       Bm.reshape(T, G, N), Cm.reshape(T, G, N), p["D"], mm)
    gated = y.reshape(T, Dn) * jax.nn.silu(z)
    return mm("tf,fc->tc", group_rms_norm(gated, p["norm"], G, s["eps"]),
              p["out_proj"])


# -- the attention layer ------------------------------------------------------

def attention_layer(u, p, s: dict, mm, q_block: int):
    T = u.shape[0]
    H, Hk, D = s["heads"], s["kv_heads"], s["head_dim"]
    q = mm("tc,cf->tf", u, p["wq"]).reshape(T, H, D)
    k = mm("tc,cf->tf", u, p["wk"]).reshape(T, Hk, D)
    v = mm("tc,cf->tf", u, p["wv"]).reshape(T, Hk, D)
    o = attention(q, k, v, None, mm, q_block)
    return mm("tf,fc->tc", o.reshape(T, H * D), p["wo"])


# -- the expert layer ---------------------------------------------------------

def relu2(a):
    return jnp.square(jax.nn.relu(a))


def route(u, router, bias, s: dict, mm):
    """(experts [T, K], gates [T, K]): the ``top_k`` largest of
    sigmoid(u W_r) + bias; the gates are the scores alone, over their sum,
    times ``route_scale``."""
    scores = jax.nn.sigmoid(mm("tc,ce->te", u, router))
    _, chosen = jax.lax.top_k(scores + bias, s["top_k"])
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if s["route_norm"]:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return chosen, gates * s["route_scale"]


def experts(latent, p, chosen, gates, first: int, mm):
    """What the held experts ``first .. first + len(w1)`` add in the
    latent for tokens routed as (chosen, gates). Dense: each held expert
    on every token, times the token's gate for it (zero where not
    chosen)."""
    held = p["w1"].shape[0]

    @jax.checkpoint
    def one(y, args):
        e, w1, w2 = args
        gate = jnp.where(chosen == e, gates, 0.0).sum(-1)         # [T]
        hidden = relu2(mm("tc,cf->tf", latent, w1))
        return y + gate[:, None] * mm("tf,fc->tc", hidden, w2), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(latent),
                        (first + jnp.arange(held), p["w1"], p["w2"]))
    return y


def shared_expert(u, p, mm):
    return mm("tf,fc->tc", relu2(mm("tc,cf->tf", u, p["shared_w1"])),
              p["shared_w2"])


def moe(u, p, bias, s: dict, mm):
    """(F [T, d], counts [E] of token-choices per expert over ALL the
    experts)."""
    chosen, gates = route(u, p["router"], bias, s, mm)
    latent = mm("tc,cl->tl", u, p["w_down"])
    y = mm("tl,lc->tc",
           experts(latent, p, chosen, gates, s["expert_first"], mm),
           p["w_up"])
    if s["d_shared"]:
        y = y + shared_expert(u, p, mm)
    counts = jnp.zeros((s["experts"],), jnp.float32).at[
        chosen.reshape(-1)].add(1.0)
    return y, counts


def layer(x, p, bias, i: int, s: dict, mm, q_block: int):
    """Layer ``i`` on x [T, d]: (y, counts [E] or None)."""
    u = rms_norm(x, p["norm"]["scale"], s["eps"])
    kind, counts = s["kinds"][i], None
    if kind == MAMBA:
        out = mamba(u, p["mixer"], s, mm)
    elif kind == ATTENTION:
        out = attention_layer(u, p["mixer"], s, mm, q_block)
    else:
        out, counts = moe(u, p["moe"], bias, s, mm)
    return x + out, counts


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
        x = params["embed"][toks[:-1]]
        for i in range(len(s["kinds"])):
            name = f"h{i}"
            bias = (biases[name]["moe"]["bias"] if name in biases else None)
            x, n = jax.checkpoint(functools.partial(
                layer, i=i, s=s, mm=mm, q_block=q_block))(
                x, params[name], bias)
            if n is not None:
                counts = {**counts, name: counts[name] + n}
        return (total + head(x, toks[1:]), counts), None

    zero = {name: jnp.zeros((s["experts"],), jnp.float32)
            for name in expert_layers(s)}
    (total, counts), _ = jax.lax.scan(row, (jnp.float32(0), zero), tokens)
    return total, counts


def train_steps(seed, batches, s: dict, opt: dict, micro_rows: int,
                precision: str = "float32", q_block: int = 256):
    """Follow the first ``len(batches)`` steps of training from ``seed``:
    AdamW behind a clip by the global norm, under ``warmup_schedule``, then
    the bias update, written out in full. ``batches`` is [steps, rows,
    T + 1]; a step's gradient is the mean over all its rows' tokens (rows
    are walked one at a time inside ``loss_sum``; ``micro_rows`` is taken
    as the whole batch). Returns what lib/reference_gpt2.py ``train_steps``
    returns, with the routers' biases among the leaves of ``delta_norm``
    (they start at zero: a bias's change is the bias)."""
    steps, rows, width = batches.shape
    del micro_rows
    n_tok = rows * (width - 1)
    lr_at = warmup_schedule(opt)
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
        lr = lr_at(t)
        t = t + 1
        c1, c2 = 1 - opt["b1"] ** t, 1 - opt["b2"] ** t
        m = jax.tree.map(
            lambda a, b_: opt["b1"] * a + (1 - opt["b1"]) * clip * b_, m, g)
        v = jax.tree.map(
            lambda a, b_: opt["b2"] * a + (1 - opt["b2"]) * (clip * b_) ** 2,
            v, g)
        p = jax.tree.map(
            lambda w, a, b_: w - lr * (
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
