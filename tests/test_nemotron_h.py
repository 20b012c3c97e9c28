"""``horovod_tpu.models.HybridMambaMoE`` (one sublayer a layer by
``hybrid_override_pattern``: Mamba-2 through ``hvd.ssd_scan``, attention
with no position, two-matrix ``relu2`` experts walked in a latent beside a
shared expert) against the plain reference
(benchmarks/lib/reference_nemotron_h.py) on seeded random weights at a
small size; the reference's two scans against each other; the two-matrix
walk against a dense mask; the shares of every layer kind adding up to the
uncut layer; the configuration read from the catalog row's own keys."""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from benchmarks.lib import reference_nemotron_h as ref
from benchmarks.lib.reference_gpt2 import _mm
from horovod_tpu.models import HybridMambaMoE, HybridMambaMoEConfig
from horovod_tpu.models import hybrid_mamba_moe as hybrid
from horovod_tpu.moe import layer as moe
from horovod_tpu.monitor.registry import counter

# Five layers of every kind; 4 Mamba heads of 8 over 2 groups, 16 states,
# chunks of 16 on 64 tokens; 4 query heads on 2 KV heads; 16 experts, 3 a
# token, of which this "chip" holds 4..7, in a latent of 32.
CFG = {"model_type": "nemotron_h", "hybrid_override_pattern": "MEM*EME",
       "num_hidden_layers": 7, "layers": [0, 1, 2, 3, 4], "hidden_size": 64,
       "layer_norm_epsilon": 1e-5, "mamba_num_heads": 4, "mamba_head_dim": 8,
       "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4,
       "chunk_size": 16, "time_step_min": 0.001, "time_step_max": 0.1,
       "time_step_floor": 0.0001, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "n_routed_experts": 16,
       "num_local_experts": 4, "first_local_expert": 4,
       "num_experts_per_tok": 3, "moe_intermediate_size": 48,
       "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 96,
       "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
       "norm_topk_prob": True, "routed_scaling_factor": 5,
       "load_balance_coeff": 0.001, "vocab_size": 96,
       "max_position_embeddings": 64, "tie_word_embeddings": False}
T = 64
SIZES = ref.sizes_from_config(CFG)
MM = _mm("float32")
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL_FILE = "benchmarks/configs/nemotron-3-super-120b-a12b.json"


def _tokens(seed):
    return jax.random.randint(jax.random.key(seed), (1, T + 1), 0,
                              CFG["vocab_size"])


def _params(seed, sizes=SIZES):
    return jax.jit(functools.partial(ref.make_params, s=sizes))(
        jnp.uint32(seed))


def _program_loss(model, toks, biases):
    def loss(p):
        logits = model.apply({"params": p, "router_bias": biases},
                             toks[:, :-1])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.take_along_axis(logp, toks[:, 1:, None], -1).sum()
    return loss


def _model(dtype=jnp.float32, **overrides):
    return HybridMambaMoE(HybridMambaMoEConfig.from_dict(
        CFG, dtype=dtype, **overrides))


def _leaf_gaps(got, want):
    """{leaf: |got - want|_max / |want|_max}."""
    return {k: float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30))
            for (k, a), b in zip(ref.path_dict(got).items(),
                                 ref.path_dict(want).values())}


@pytest.fixture(scope="module")
def reference_side():
    params, toks, biases = _params(3), _tokens(1), ref.zero_biases(SIZES)
    (loss, counts), grads = jax.value_and_grad(functools.partial(
        ref.loss_sum, s=SIZES, q_block=32), has_aux=True)(
        params, biases, toks)
    return params, toks, biases, loss, counts, grads


# -- the model against the reference ------------------------------------------

def test_loss_and_every_gradient_are_the_references(reference_side):
    """The program in float32 at ``highest``: the same arithmetic in another
    order (chunks against a token loop, a walk against a dense mask, flash
    blocks against whole rows), so 2e-4 of a leaf's largest entry; the
    loss, a sum over 64 tokens, to 1e-5."""
    params, toks, biases, want, _, want_grads = reference_side
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(
            _program_loss(_model(), toks, biases))(params)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    assert jax.tree.structure(grads) == jax.tree.structure(want_grads)
    gaps = _leaf_gaps(grads, want_grads)
    assert max(gaps.values()) < 2e-4, max(gaps.items(), key=lambda g: g[1])
    # every leaf takes a gradient: nothing is cut off the tape
    assert all(float(jnp.abs(g).max()) > 0
               for g in jax.tree.leaves(want_grads))


def test_bfloat16_fails_that_tolerance(reference_side):
    """What the tolerance above is worth: the configuration's own bfloat16
    (activations and matmul operands; the scan's decays and states stay
    float32) is 30 times outside it on some leaf, so a lower precision
    than float32 cannot hide inside it."""
    params, toks, biases, _, _, want_grads = reference_side
    grads = jax.grad(_program_loss(_model(jnp.bfloat16), toks, biases))(
        params)
    assert max(_leaf_gaps(grads, want_grads).values()) > 30 * 2e-4


def test_load_is_the_references_counts_and_moves_the_bias(reference_side):
    params, toks, biases, _, counts, _ = reference_side
    model = _model(return_load=True, return_hidden=True)
    with jax.default_matmul_precision("highest"):
        _, loads = model.apply({"params": params, "router_bias": biases},
                               toks[:, :-1])
    assert set(loads) == set(counts) == {"h1", "h4"}
    for name in loads:
        np.testing.assert_array_equal(loads[name], counts[name])
        assert float(loads[name].sum()) == T * CFG["num_experts_per_tok"]
    moved = hybrid.update_router_biases(biases, loads, coeff=0.001)
    want = ref.bias_update(biases["h1"]["moe"]["bias"], counts["h1"], 0.001)
    np.testing.assert_allclose(moved["h1"]["moe"]["bias"], want, atol=1e-9)
    # no gradient reaches the bias
    g = jax.grad(lambda b: model.apply(
        {"params": params, "router_bias": b}, toks[:, :-1])[0].sum())(biases)
    assert all(float(jnp.abs(x).max()) == 0 for x in jax.tree.leaves(g))


def test_init_makes_the_references_tree():
    model = _model()
    made = jax.eval_shape(model.init, jax.random.key(0),
                          jax.ShapeDtypeStruct((1, T), jnp.int32))
    want = jax.eval_shape(functools.partial(ref.make_params, s=SIZES),
                          jax.ShapeDtypeStruct((), jnp.uint32))
    assert jax.tree.structure(made["params"]) == jax.tree.structure(want)
    assert [a.shape for a in jax.tree.leaves(made["params"])] \
        == [a.shape for a in jax.tree.leaves(want)]
    assert jax.tree.structure(made["router_bias"]) \
        == jax.tree.structure(ref.zero_biases(SIZES))


# -- the reference's two scans ------------------------------------------------

@pytest.mark.parametrize("L", [8, 16, 64])
def test_the_references_chunked_form_is_its_token_loop(L):
    rs = np.random.RandomState(7)
    h, Pd, G, N = 4, 8, 2, 16
    xs = jnp.asarray(rs.randn(T, h, Pd), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rs.randn(T, h), jnp.float32) - 2)
    A_log = jnp.log(jnp.asarray(rs.uniform(1, 16, (h,)), jnp.float32))
    Bm, Cm = (jnp.asarray(rs.randn(T, G, N), jnp.float32) for _ in "bc")
    D = jnp.asarray(rs.randn(h), jnp.float32)
    loop = ref.ssd_token_loop(xs, dt, A_log, Bm, Cm, D, MM)
    chunked = ref.ssd_chunked(xs, dt, A_log, Bm, Cm, D, L)
    assert float(jnp.abs(loop - chunked).max()) \
        < 1e-5 * float(jnp.abs(loop).max())
    # ... and the program's op is the same scan
    with jax.default_matmul_precision("highest"):
        op = hvd.ssd_scan(xs[None], dt[None], A_log, Bm[None], Cm[None], D,
                          chunk=L)[0]
    assert float(jnp.abs(loop - op).max()) < 1e-5 * float(jnp.abs(loop).max())


# -- the two-matrix walk ------------------------------------------------------

def _walk_case(seed=0, N=64, C=16, F=24, E=16, held=4, K=3):
    ks = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(ks[0], (N, C)),
            jax.random.normal(ks[1], (held, C, F)) * 0.3,
            jax.random.normal(ks[2], (held, F, C)) * 0.3,
            jax.random.normal(ks[3], (N, 32)),
            jax.random.normal(ks[4], (32, E)))


def _walked(x, w1, w2, u, router, K=3, first=4):
    plan = moe.moe_route(u, router, experts_per_token=K, first_expert=first,
                         held=w1.shape[0], scoring="sigmoid", route_scale=5.0)
    return moe.moe_apply(x, plan, {"w1": w1, "w2": w2}, activation="relu2")


def _dense(x, w1, w2, u, router, K=3, first=4):
    s = dict(top_k=K, route_norm=True, route_scale=5.0)
    chosen, gates = ref.route(u, router, 0.0, s, MM)
    return ref.experts(x, {"w1": w1, "w2": w2}, chosen, gates, first, MM)


def test_the_two_matrix_walk_is_the_dense_mask_in_both_directions():
    """Rows 16 wide routed by a router that read 32: the plan knows rows,
    not widths. Float32 at ``highest``, sums in another order: 1e-5."""
    case = _walk_case()
    w = jax.random.normal(jax.random.key(9), case[0].shape)
    before = counter("moe.activation", kind="relu2").value
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(
            lambda *a: (_walked(*a) * w).sum(), argnums=range(5))(*case)
        want, want_grads = jax.value_and_grad(
            lambda *a: (_dense(*a) * w).sum(), argnums=range(5))(*case)
    assert counter("moe.activation", kind="relu2").value > before
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    for a, b in zip(grads, want_grads):
        assert float(jnp.abs(a - b).max()) < 1e-5 * float(jnp.abs(b).max())


def test_a_gated_walk_traces_the_matmuls_it_always_did():
    """``w3`` present: three grouped matmuls forward and five more in the
    backward (the hidden rows made again, ``dy W2^T``, two transposes), as
    before the two-matrix form; without it two and three."""
    x, w1, w2, u, router = _walk_case()
    plan = moe.moe_route(u, router, experts_per_token=3, first_expert=4,
                         held=4)

    def count(params, act):
        return str(jax.make_jaxpr(jax.grad(lambda x_: moe.moe_apply(
            x_, plan, params, activation=act).sum()))(x)).count(
                "= ragged_dot")

    assert count({"w1": w1, "w3": w1, "w2": w2}, "silu") == 3 + 5
    assert count({"w1": w1, "w2": w2}, "relu2") == 2 + 3


@pytest.mark.parametrize("act", ["silu", "relu"])
def test_a_gated_walk_is_its_dense_form(act):
    x, w1, w2, u, router = _walk_case(seed=1)
    w3 = jax.random.normal(jax.random.key(4), w1.shape) * 0.3
    fn = {"silu": jax.nn.silu, "relu": jax.nn.relu}[act]
    with jax.default_matmul_precision("highest"):
        plan = moe.moe_route(x, jnp.pad(router, ((0, 0), (0, 0)))[:16],
                             experts_per_token=3, first_expert=4, held=4)
        got = moe.moe_apply(x, plan, {"w1": w1, "w3": w3, "w2": w2},
                            activation=act)
        probs = jax.nn.softmax(x @ router[:16], -1)
        top, chosen = jax.lax.top_k(probs, 3)
        gates = top / top.sum(-1, keepdims=True)
        want = sum(
            jnp.where(chosen == e + 4, gates, 0).sum(-1)[:, None]
            * ((fn(x @ w1[e]) * (x @ w3[e])) @ w2[e]) for e in range(4))
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max())


def test_an_unknown_activation_is_refused():
    x, w1, w2, u, router = _walk_case()
    plan = moe.moe_route(u, router, experts_per_token=3, held=4)
    with pytest.raises(ValueError, match="relu2"):
        moe.moe_apply(x, plan, {"w1": w1, "w2": w2}, activation="gelu")


# -- the shares add up --------------------------------------------------------

# The uncut layers at a small size: 16 Mamba heads of 4 in 8 groups; 8
# query heads on 2 KV heads; 64 experts, 6 a token.
WHOLE = dict(CFG, hidden_size=32, mamba_num_heads=16, mamba_head_dim=4,
             n_groups=8, ssm_state_size=8, num_attention_heads=8,
             num_key_value_heads=2, head_dim=8, n_routed_experts=64,
             num_local_experts=64, first_local_expert=0,
             num_experts_per_tok=6, moe_intermediate_size=24,
             moe_latent_size=16, moe_shared_expert_intermediate_size=40)


def _layer_input(seed=11, d=32):
    return jax.random.normal(jax.random.key(seed), (T, d))


def _whole_params(kind):
    sizes = ref.sizes_from_config(dict(WHOLE, hybrid_override_pattern=kind,
                                       num_hidden_layers=1, layers=[0]))
    tree = _params(5, sizes)["h0"]
    return sizes, tree["moe" if kind == "E" else "mixer"]


def test_eight_head_and_group_shares_of_a_mamba_layer_add_up():
    sizes, p = _whole_params("M")
    u = _layer_input()
    want = ref.mamba(u, p, sizes, MM)
    h, Pd, G, N = 16, 4, 8, 8
    Dn = h * Pd
    cfg = HybridMambaMoEConfig.from_dict(
        dict(WHOLE, mamba_num_heads=h // 8, n_groups=1,
             published={"mamba_num_heads": h, "n_groups": G}),
        dtype=jnp.float32)
    layer = hybrid._Mamba2(cfg)
    total = 0
    for i in range(8):
        ch = slice(i * Dn // 8, (i + 1) * Dn // 8)       # the share's channels
        heads = slice(i * h // 8, (i + 1) * h // 8)
        cols = np.concatenate([
            np.arange(Dn)[ch], Dn + np.arange(Dn)[ch],             # z, xs
            2 * Dn + i * N + np.arange(N),                         # B
            2 * Dn + G * N + i * N + np.arange(N),                 # C
            2 * Dn + 2 * G * N + np.arange(h)[heads]])             # dt
        conv = cols[Dn // 8:Dn // 8 + Dn // 8 + 2 * N] - Dn
        share = {"in_proj": p["in_proj"][:, cols],
                 "conv_w": p["conv_w"][:, conv], "conv_b": p["conv_b"][conv],
                 "dt_bias": p["dt_bias"][heads], "A_log": p["A_log"][heads],
                 "D": p["D"][heads], "norm": p["norm"][ch],
                 "out_proj": p["out_proj"][ch]}
        with jax.default_matmul_precision("highest"):
            total = total + layer.apply({"params": share}, u[None])[0]
    assert float(jnp.abs(total - want).max()) < 1e-5 * float(
        jnp.abs(want).max())


def test_eight_head_shares_of_an_attention_layer_add_up():
    sizes, p = _whole_params("*")
    u = _layer_input(12)
    want = ref.attention_layer(u, p, sizes, MM, q_block=32)
    H, Hk, D = 8, 2, 8
    cfg = HybridMambaMoEConfig.from_dict(
        dict(WHOLE, num_attention_heads=1, num_key_value_heads=1,
             published={"num_attention_heads": H,
                        "num_key_value_heads": Hk}), dtype=jnp.float32)
    layer = hybrid._Attention(cfg)
    total = 0
    for i in range(8):
        q = slice(i * D, (i + 1) * D)
        kv = slice((i // 4) * D, (i // 4 + 1) * D)       # KV head i // 4
        share = {"wq": p["wq"][:, q], "wk": p["wk"][:, kv],
                 "wv": p["wv"][:, kv], "wo": p["wo"][q]}
        with jax.default_matmul_precision("highest"):
            total = total + layer.apply({"params": share}, u[None])[0]
    assert float(jnp.abs(total - want).max()) < 1e-5 * float(
        jnp.abs(want).max())


def test_sixty_four_expert_shares_of_an_expert_layer_add_up():
    """Every share routes over all 64 experts, walks its own and adds the
    router-independent parts whole: the 64 results sum to the uncut layer
    with the shared expert counted once (63 copies taken off). A share's
    experts are brought to the front by rolling the router's columns, so
    that one traced layer serves all 64."""
    sizes, p = _whole_params("E")
    # At this width normal(0.02) weights through a squared ReLU leave the
    # routed part a thousandth of the shared one: scaled up to be seen.
    p = dict(p, **{k: p[k] * 4 for k in ("w_down", "w1", "w2", "w_up")})
    u = _layer_input(13)
    bias = jnp.zeros((64,))
    want, counts = ref.moe(u, p, bias, sizes, MM)
    cfg = HybridMambaMoEConfig.from_dict(
        dict(WHOLE, num_local_experts=1,
             published={"num_local_experts": 64}), dtype=jnp.float32)
    layer = hybrid._LatentMoE(cfg)

    @jax.jit
    def one(share):
        with jax.default_matmul_precision("highest"):
            return layer.apply({"params": share,
                                "router_bias": {"bias": bias}}, u[None])

    total = 0
    for i in range(64):
        share = dict(p, router=jnp.roll(p["router"], -i, axis=1),
                     w1=p["w1"][i:i + 1], w2=p["w2"][i:i + 1])
        out, load = one(share)
        total = total + out[0]
        np.testing.assert_array_equal(jnp.roll(load, i), counts)
    shared = ref.shared_expert(u, p, MM)
    total = total - 63 * shared
    # float32 sums of 64 copies of the shared part, 63 taken off again:
    # 1e-6 of what was summed
    assert float(jnp.abs(total - want).max()) < 1e-6 * 64 * float(
        jnp.abs(shared).max()) + 1e-5 * float(jnp.abs(want).max())
    # ... and the routed part is there to be missed: a tenth of the whole
    assert float(jnp.abs(want - shared).max()) > 0.1 * float(
        jnp.abs(want).max())


# -- the configuration --------------------------------------------------------

def _catalog_row():
    with open(CATALOG_FILE) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16":
                return row
    pytest.skip("the catalog has no such row here")


def test_from_dict_reads_the_published_config():
    cfg = HybridMambaMoEConfig.from_dict(_catalog_row()["config"])
    assert len(cfg.pattern) == 88 and cfg.pattern.count("*") == 8
    assert (cfg.pattern.count("M"), cfg.pattern.count("E")) == (40, 40)
    assert (cfg.d_inner, cfg.conv_width, cfg.in_width) == (8192, 10240, 18560)
    assert (cfg.num_local_experts, cfg.routed_scaling_factor) == (512, 5.0)
    assert cfg.d_inner // cfg.n_groups == 1024 and not cfg.has_router_bias()


def test_the_cells_file_builds_one_period_of_an_eighth():
    import os
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), CELL_FILE)) as f:
        cell = json.load(f)
    cfg = HybridMambaMoEConfig.from_dict(cell)
    assert cfg.pattern == "MEMEMEMEM*E"
    assert (cfg.mamba_num_heads, cfg.n_groups, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.num_local_experts) == (16, 1, 4, 1, 8)
    assert cfg.d_inner // cfg.n_groups == 1024       # the group's size stays
    assert cfg.has_router_bias()


@pytest.mark.parametrize("change, error", [
    (dict(model_type="phi4flash"), ValueError),
    (dict(hybrid_override_pattern="MEM"), ValueError),
    (dict(hybrid_override_pattern="MEMXEME"), ValueError),
    (dict(mamba_num_heads=3), ValueError),
    (dict(num_attention_heads=3), ValueError),
    (dict(first_local_expert=14), ValueError),
    (dict(n_group=2), NotImplementedError),
    (dict(mlp_hidden_act="silu"), NotImplementedError),
    (dict(published={"mamba_num_heads": 6}), ValueError),
    # heads an eighth, groups a half: a group's heads would be split
    (dict(published={"mamba_num_heads": 32, "n_groups": 4}), ValueError)])
def test_a_configuration_that_cannot_be_built_is_refused(change, error):
    with pytest.raises(error):
        HybridMambaMoEConfig.from_dict(dict(CFG, **change))


# -- what a rematerialised layer keeps, and the counters ----------------------

def test_candidates_name_what_each_kind_would_make_again():
    cfg = HybridMambaMoEConfig.from_dict(CFG)
    cand = hybrid.remat_candidates(cfg, 1, T)
    assert list(cand) == [moe.PLAN_NAME, hybrid.LATENT_OUT_NAME,
                          hybrid.SSM_IN_NAME, hybrid.LATENT_NAME,
                          hybrid.QKV_NAME, hybrid.MLP_HIDDEN_NAME]
    kinds = cfg.pattern                                    # MEM*E
    for name, by in cand.items():
        holder = {hybrid.SSM_IN_NAME: "M", hybrid.QKV_NAME: "*"}.get(
            name, "E")
        assert [b > 0 for b in by] == [k == holder for k in kinds], name
    row = T * 2
    assert cand[hybrid.SSM_IN_NAME][0] == row * cfg.in_width
    assert cand[hybrid.LATENT_NAME][1] == row * 32
    assert cand[hybrid.MLP_HIDDEN_NAME][4] == row * 96
    # with a chip's memory everything is kept; with none of it, nothing
    assert hybrid.remat_kept(cfg, 1, T) == cand
    assert hybrid.remat_kept(cfg, 1, T, memory_bytes=1) == {}


def test_the_cells_layers_keep_every_candidate_within_an_eighth():
    cfg = HybridMambaMoEConfig.from_dict(dict(
        CFG, hybrid_override_pattern="MEMEMEMEM*E", num_hidden_layers=11,
        layers=list(range(11)), hidden_size=4096, mamba_num_heads=16,
        mamba_head_dim=64, n_groups=1, ssm_state_size=128, chunk_size=128,
        num_attention_heads=4, num_key_value_heads=1, head_dim=128,
        n_routed_experts=512, num_local_experts=8, first_local_expert=0,
        num_experts_per_tok=22, moe_intermediate_size=2688,
        moe_latent_size=1024, moe_shared_expert_intermediate_size=5376))
    anyway = hybrid.remat_kept_anyway(cfg, 1, 8192)
    cand = hybrid.remat_candidates(cfg, 1, 8192)
    kept = hybrid.remat_kept(cfg, 1, 8192, memory_bytes=16 * 2 ** 30)
    assert kept == cand
    assert anyway + sum(map(sum, cand.values())) < 0.125 * 16 * 2 ** 30
    # 11 inputs of 67 MB, 5 scan outputs with 64 chunk states, 1 flash out
    assert anyway == 11 * 8192 * 4096 * 2 + 5 * (
        8192 * 1024 * 2 + 64 * 1024 * 128 * 4) + 8192 * 4 * (128 * 2 + 4)


def test_counters_say_what_a_traced_model_holds():
    names = {"chunks": counter("ssd.chunks"),
             "held": counter("moe.choices_held"),
             "relu2": counter("moe.activation", kind="relu2"),
             "sigmoid": counter("moe.scoring", kind="sigmoid")}
    before = {k: c.value for k, c in names.items()}
    model = _model()
    jax.eval_shape(model.init, jax.random.key(0),
                   jax.ShapeDtypeStruct((1, T), jnp.int32))
    grew = {k: c.value - before[k] for k, c in names.items()}
    # 2 Mamba layers of 4 chunks; 2 expert layers: 64 x 3 x 4 / 16 choices
    assert grew == {"chunks": 8, "held": 2 * 48, "relu2": 2, "sigmoid": 2}


def test_the_models_are_exported():
    assert hvd.models.HybridMambaMoE is HybridMambaMoE
    assert hvd.ssd_scan is hybrid._ssd.ssd_scan
    assert "relu2" in moe.ACTIVATIONS
    assert dataclasses.is_dataclass(HybridMambaMoEConfig)
