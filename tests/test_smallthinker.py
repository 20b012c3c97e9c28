"""SmallThinker through ``horovod_tpu.models.SparseMoEDecoder`` (a router
that reads the block's input ahead of attention, ReLU-gated experts, a NoPE
full layer and sliding layers at 7 query heads a KV head) against the plain
reference (benchmarks/lib/reference_smallthinker.py) on seeded random
weights at a small size; the expert layer's two halves (``hvd.moe_route``,
``hvd.moe_apply``) and the walk's ReLU against plain ``jax.numpy``; the
configuration read from the catalog row's own keys."""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from benchmarks.lib import reference_smallthinker as ref
from horovod_tpu.models import SparseMoEConfig, SparseMoEDecoder
from horovod_tpu.models import sparse_moe_decoder as decoder
from horovod_tpu.moe import layer as moe
from horovod_tpu.monitor.registry import counter

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark"))
from bench_tiny_smallthinker import CATALOG  # noqa: E402

# A full layer, then a sliding one (window 16 of 64 tokens); 7 query heads
# on one KV head; 8 experts, 2 a token, of which this "chip" holds 2..5.
CFG = {"model_name": "smallthinker_test", "layers": 2, "num_hidden_layers": 2,
       "hidden_size": 64, "num_attention_heads": 7, "num_key_value_heads": 1,
       "head_dim": 16, "vocab_size": 96, "max_position_embeddings": 64,
       "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 8,
       "moe_num_active_primary_experts": 2,
       "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
       "num_local_experts": 4, "first_local_expert": 2,
       "sliding_window_layout": [0, 1], "rope_layout": [0, 1],
       "sliding_window_size": 16, "rms_norm_eps": 1e-6,
       "rope_theta": 1500000, "rope_scaling": None,
       "tie_word_embeddings": False}
T = 64
SIZES = ref.sizes_from_config(CFG)

CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"


def _tokens(seed):
    return jax.random.randint(jax.random.key(seed), (1, T + 1), 0,
                              CFG["vocab_size"])


def _params(seed, sizes=SIZES):
    return jax.jit(functools.partial(ref.make_params, s=sizes))(
        jnp.uint32(seed))


def _program_loss(model, toks):
    def loss(p):
        logits = model.apply({"params": p}, toks[:, :-1])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.take_along_axis(logp, toks[:, 1:, None], -1).sum()
    return loss


def _model(dtype=jnp.float32, **overrides):
    return SparseMoEDecoder(SparseMoEConfig.from_dict(
        CFG, dtype=dtype, **overrides))


@pytest.fixture(scope="module")
def float32_pair():
    """(params, tokens, program (loss, grads), reference (loss, grads))
    with the program in float32 at ``highest``: the same arithmetic."""
    params, toks = _params(3), _tokens(1)
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(_program_loss(_model(), toks))(params)
    want = jax.value_and_grad(
        lambda p: ref.loss_sum(p, toks, SIZES, q_block=32))(params)
    return params, toks, got, want


# -- the model against the reference ------------------------------------------

def test_parameter_tree_is_the_references():
    """No q / k norm, no gate, no shared expert, no indexer: the leaves are
    the reference's, router and experts under ``moe`` as in every family."""
    want = jax.eval_shape(_model().init, jax.random.key(0),
                          jax.ShapeDtypeStruct((1, T), jnp.int32))["params"]
    got = jax.eval_shape(functools.partial(ref.make_params, s=SIZES),
                         jax.ShapeDtypeStruct((), jnp.uint32))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert sorted(got["h0"]["attn"]) == ["wk", "wo", "wq", "wv"]
    assert sorted(got["h0"]["moe"]) == ["router", "w1", "w2", "w3"]


def test_logits_are_the_references(float32_pair):
    """float32 against float32 at ``highest``: 2e-6 of the largest logit
    (sums in another order through two layers)."""
    params, toks, _, _ = float32_pair
    with jax.default_matmul_precision("highest"):
        got = _model().apply({"params": params}, toks[:, :-1])[0]
    want = ref.logits(params, toks[0, :-1], SIZES, q_block=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6 * float(jnp.abs(want).max()))


def test_loss_is_the_references(float32_pair):
    """float32 against float32: 1e-6 relative (sums in another order)."""
    _, _, (loss, _), (want, _) = float32_pair
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)


LEAVES = sorted(ref.path_dict(jax.eval_shape(
    functools.partial(ref.make_params, s=SIZES),
    jax.ShapeDtypeStruct((), jnp.uint32))))
#: Every gradient leaf to this share of the leaf's largest entry: float32
#: rounding through two layers reads to 3e-6 here; bfloat16 activations
#: where float32 is stated read 1e-3 and more on every matrix (the test
#: below holds that).
LEAF_TOL = 1e-5


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_is_the_references(float32_pair, leaf):
    _, _, (_, got), (_, want) = float32_pair
    a, b = ref.path_dict(got)[leaf], ref.path_dict(want)[leaf]
    assert float(jnp.abs(b).max()) > 0
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=LEAF_TOL * float(jnp.abs(b).max()))


@pytest.mark.parametrize("hidden, form", [(64, "scatter"),
                                          (128, "sorted_rows_kernel")])
def test_the_lookup_with_its_own_backward_is_the_lines_it_replaced(
        monkeypatch, hidden, form):
    """``ops/embed_lookup.py`` against ``embed.astype(dtype)[tokens]``
    differentiated by JAX, in float32: the loss and every gradient leaf to
    the leaves' tolerance, the rows counted under the form their width
    takes."""
    cfg = dict(CFG, hidden_size=hidden)
    params, toks = _params(4, ref.sizes_from_config(cfg)), _tokens(6)
    model = SparseMoEDecoder(SparseMoEConfig.from_dict(cfg,
                                                       dtype=jnp.float32))
    rows = counter("embed.grad_rows", form=form)
    before = rows.value
    loss, got = jax.value_and_grad(_program_loss(model, toks))(params)
    assert rows.value - before == T

    monkeypatch.setattr(decoder, "embed_lookup",
                        lambda table, tokens, dt: table.astype(dt)[tokens])
    want_loss, want = jax.value_and_grad(_program_loss(model, toks))(params)
    assert rows.value - before == T
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    got, want = ref.path_dict(got), ref.path_dict(want)
    for leaf, b in want.items():
        np.testing.assert_allclose(
            np.asarray(got[leaf]), np.asarray(b), err_msg=leaf,
            atol=LEAF_TOL * float(jnp.abs(b).max()))
    assert float(jnp.abs(want["embed"]).max()) > 0


def test_bfloat16_where_float32_is_stated_fails_the_tolerances(float32_pair):
    """The tolerances above are tight enough to tell a precision: the model
    with bfloat16 activations fails the loss's and most leaves'."""
    params, toks, _, (want, wgrads) = float32_pair
    loss, grads = jax.value_and_grad(
        _program_loss(_model(jnp.bfloat16), toks))(params)
    assert abs(float(loss) - float(want)) > 1e-6 * float(want) * 10
    got, wanted = ref.path_dict(grads), ref.path_dict(wgrads)
    failing = [leaf for leaf in LEAVES if float(
        jnp.abs(got[leaf] - wanted[leaf]).max())
        > LEAF_TOL * float(jnp.abs(wanted[leaf]).max())]
    assert len(failing) > len(LEAVES) // 2, failing


def test_bfloat16_model_tracks_the_reference():
    """The model as the benchmark runs it (bf16 activations): the loss
    within 2e-3 a token of the float32 reference's, no leaf's gradient norm
    further than 6% of max(leaf, median leaf): top-2 of 8 choices flip near
    their threshold at this size."""
    params, toks = _params(5), _tokens(2)
    loss, grads = jax.value_and_grad(
        _program_loss(_model(jnp.bfloat16), toks))(params)
    want, wgrads = jax.value_and_grad(
        lambda p: ref.loss_sum(p, toks, SIZES, q_block=32))(params)
    assert abs(float(loss) - float(want)) / T < 2e-3
    got, ref_norms = ref.leaf_norms(grads), ref.leaf_norms(wgrads)
    floor = float(np.median([float(v) for v in ref_norms.values()]))
    for leaf, n in ref_norms.items():
        gap = abs(float(got[leaf]) - float(n)) / max(float(n), floor)
        assert gap < 0.06, (leaf, gap)


def test_routing_from_the_block_input_is_not_routing_from_the_mlp_input(
        float32_pair):
    """The test that would catch the router put back where the other
    families have it: on the same weights the two routings choose other
    experts (the loads differ), the logits differ, and only the
    block-input model is the reference's."""
    params, toks, _, _ = float32_pair
    x = toks[:, :-1]
    want = ref.logits(params, toks[0, :-1], SIZES, q_block=32)
    out = {}
    for at in decoder.ROUTER_INPUTS:
        model = SparseMoEDecoder(dataclasses.replace(
            _model().cfg, router_input=at, return_load=True))
        with jax.default_matmul_precision("highest"):
            out[at] = model.apply({"params": params}, x)
    (block, block_loads), (mlp, mlp_loads) = (out["block_input"],
                                              out["mlp_input"])
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(block[0] - want).max()) < 2e-6 * scale
    assert float(jnp.abs(mlp[0] - want).max()) > 1e-3 * scale
    # The first layer's input is the embedding either way, but its router
    # reads it un-normed and before attention here.
    for layer in ("h0", "h1"):
        assert float(block_loads[layer].sum()) == 2 * T
        assert not np.array_equal(np.asarray(block_loads[layer]),
                                  np.asarray(mlp_loads[layer]))


# -- the four shares of a layer -----------------------------------------------

def test_four_shares_add_up_to_the_uncut_layer():
    """Four chips of a stage hold two experts each of this size's eight
    (16 of 64 at the cell's): every share's block output is h + its
    experts' part, so the four add up to the uncut reference's layer with
    attention and the router counted once: sum_k y_k - 3 h."""
    uncut = dict(SIZES, experts_held=8, expert_first=0)
    layer = _params(7, uncut)["h1"]                 # the sliding layer
    x = 0.5 * jax.random.normal(jax.random.key(8), (T, 64), jnp.float32)
    mm = ref._mm("float32")
    want = ref.block(x, layer, 1, uncut, mm, 32)
    no_experts = jax.tree.map(jnp.zeros_like, layer["moe"])
    h = ref.block(x, dict(layer, moe=dict(no_experts,
                                          router=layer["moe"]["router"])),
                  1, uncut, mm, 32)
    total, loads = -3 * h, []
    for first in (0, 2, 4, 6):
        cfg = SparseMoEConfig.from_dict(
            dict(CFG, num_local_experts=2, first_local_expert=first),
            dtype=jnp.float32)
        share = dict(layer, moe=dict(
            router=layer["moe"]["router"],
            **{n: layer["moe"][n][first:first + 2]
               for n in ("w1", "w3", "w2")}))
        with jax.default_matmul_precision("highest"):
            y, load = decoder._Block(cfg, 1).apply({"params": share},
                                                   x[None])
        total, loads = total + y[0], loads + [load]
    assert float(jnp.abs(want - h).max()) > 1e-3      # the experts matter
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-6 * float(jnp.abs(want).max()))
    for load in loads[1:]:       # every share routes over all 8, the same
        np.testing.assert_array_equal(np.asarray(load),
                                      np.asarray(loads[0]))
    assert float(loads[0].sum()) == 2 * T


# -- the expert layer's two halves --------------------------------------------

N, C, F, E, HELD, FIRST, K = 96, 32, 24, 8, 4, 2, 2


def _layer_inputs(seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (N, C), jnp.float32)
    params = {"router": 0.5 * jax.random.normal(ks[1], (C, E)),
              "w1": 0.3 * jax.random.normal(ks[2], (HELD, C, F)),
              "w3": 0.3 * jax.random.normal(ks[3], (HELD, C, F)),
              "w2": 0.3 * jax.random.normal(ks[4], (HELD, F, C))}
    logits = jax.random.normal(ks[5], (N, E), jnp.float32)
    return x, params, logits


def _plain_share(x, gates, experts, w1, w3, w2, act):
    """The plain masked loop: every held expert on every token, times the
    token's gate for it."""
    y = jnp.zeros_like(x)
    for e in range(HELD):
        gate = jnp.where(experts == FIRST + e, gates, 0.0).sum(-1)
        y = y + gate[:, None] * ((act(x @ w1[e]) * (x @ w3[e])) @ w2[e])
    return y


@pytest.mark.parametrize("activation", ["relu", "silu"])
def test_walk_is_the_plain_masked_loop(activation):
    """``_walk`` with a static activation against ``jax.grad`` of the plain
    loop: forward, dx, dgate and the three weight gradients. Held expert 3
    (global 5) receives no token; token rows 0..7 are zero and hidden unit
    0 of every expert has a zero W1 column, so gate pre-activations of
    exactly 0 are among the rows (ReLU's derivative there is 0 both ways);
    the rematerialised hidden rows of the backward use the same
    activation, or SiLU's would show in every gradient."""
    x, params, logits = _layer_inputs(1)
    x = x.at[:8].set(0.0)
    params["w1"] = params["w1"].at[:, :, 0].set(0.0)
    logits = logits.at[:, FIRST + 3].set(-1e9)
    experts = moe.moe_router(x, params["router"], topk=K,
                             router_logits=logits)[0]
    plan = hvd.moe_route(x, params["router"], experts_per_token=K,
                         first_expert=FIRST, held=HELD, router_logits=logits)
    assert int(plan.sizes[3]) == 0 and int(plan.sizes.sum()) > 0
    assert float(plan.load[FIRST + 3]) == 0
    act = moe.ACTIVATIONS[activation]
    w = jax.random.normal(jax.random.key(9), (N, C), jnp.float32)

    def walked(x, gates, w1, w3, w2):
        return hvd.moe_apply(x, plan._replace(gates=gates),
                             dict(w1=w1, w3=w3, w2=w2),
                             activation=activation)

    def plain(x, gates, w1, w3, w2):
        return _plain_share(x, gates, experts, w1, w3, w2, act)

    args = (x, plan.gates, params["w1"], params["w3"], params["w2"])
    with jax.default_matmul_precision("highest"):
        got = walked(*args)
        want = plain(*args)
        g_got = jax.grad(lambda *a: jnp.sum(walked(*a) * w),
                         argnums=range(5))(*args)
        g_want = jax.grad(lambda *a: jnp.sum(plain(*a) * w),
                          argnums=range(5))(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * float(jnp.abs(want).max()))
    for name, a, b in zip(("dx", "dgate", "dw1", "dw3", "dw2"), g_got,
                          g_want):
        assert float(jnp.abs(b).max()) > 0, name
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), err_msg=name,
            atol=1e-5 * float(jnp.abs(b).max()))
    assert not np.asarray(g_got[2])[3].any()     # the expert nobody chose
    assert not np.asarray(g_got[0])[:8].any() or activation == "silu"


def test_relu_is_not_silu_in_either_direction():
    x, params, logits = _layer_inputs(2)
    plan = hvd.moe_route(x, params["router"], experts_per_token=K,
                         first_expert=FIRST, held=HELD)
    out = {a: jax.value_and_grad(lambda x: jnp.sum(hvd.moe_apply(
        x, plan, params, activation=a) ** 2))(x) for a in moe.ACTIVATIONS}
    assert abs(float(out["relu"][0]) - float(out["silu"][0])) > 1e-2
    assert float(jnp.abs(out["relu"][1] - out["silu"][1]).max()) > 1e-2
    with pytest.raises(ValueError, match="activation"):
        hvd.moe_apply(x, plan, params, activation="gelu")
    with pytest.raises(ValueError, match="held"):
        hvd.moe_apply(x, plan, {n: params[n][:2] for n in
                                ("w1", "w3", "w2")})


@pytest.mark.parametrize("activation", ["silu", "relu"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_route_then_apply_on_one_tensor_is_moe_ffn_dropless(activation,
                                                           dtype):
    """Bit for bit: the value, the load, and the gradients of the tokens
    and of every weight, router included."""
    x, params, _ = _layer_inputs(3)
    x = x.astype(dtype)

    def whole(x, params):
        y, aux = hvd.moe_ffn_dropless(
            x, params, experts_per_token=K, first_expert=FIRST,
            activation=activation)
        return jnp.sum(y.astype(jnp.float32) ** 2), (y, aux.load)

    def halves(x, params):
        plan = hvd.moe_route(x, params["router"], experts_per_token=K,
                             first_expert=FIRST, held=HELD)
        y = hvd.moe_apply(x, plan, params, activation=activation)
        return jnp.sum(y.astype(jnp.float32) ** 2), (y, plan.load)

    got = jax.jit(jax.value_and_grad(halves, argnums=(0, 1),
                                     has_aux=True))(x, params)
    want = jax.jit(jax.value_and_grad(whole, argnums=(0, 1),
                                      has_aux=True))(x, params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert float(jnp.abs(got[1][1]["router"]).max()) > 0


def test_route_reads_its_own_tensor():
    """A plan made from one tensor and walked over another is the plain
    loop with the first tensor's routing; the router's gradient comes back
    through the gates to the tensor it read, and none of the experts'
    input's gradient is the router's."""
    x, params, _ = _layer_inputs(4)
    z = jax.random.normal(jax.random.key(11), (N, C), jnp.float32)

    def f(x, z, router):
        plan = hvd.moe_route(x, router, experts_per_token=K,
                             first_expert=FIRST, held=HELD)
        return hvd.moe_apply(z, plan, params, activation="relu")

    def plain(x, z, router):
        probs = jax.nn.softmax(x @ router, -1)
        gates, experts = jax.lax.top_k(probs, K)
        return _plain_share(z, gates / gates.sum(-1, keepdims=True), experts,
                            params["w1"], params["w3"], params["w2"],
                            jax.nn.relu)

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(lambda *a: jnp.sum(f(*a) ** 2),
                                 argnums=(0, 1, 2))(x, z, params["router"])
        want = jax.value_and_grad(lambda *a: jnp.sum(plain(*a) ** 2),
                                  argnums=(0, 1, 2))(x, z, params["router"])
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5 * float(jnp.abs(b).max()))


def test_route_refuses_what_it_cannot_do():
    x, params, _ = _layer_inputs(5)
    with pytest.raises(ValueError, match="not among"):
        hvd.moe_route(x, params["router"], experts_per_token=K,
                      first_expert=6, held=HELD)
    with pytest.raises(ValueError, match="topk"):
        hvd.moe_route(x, params["router"], experts_per_token=E + 1,
                      held=HELD)


# -- scopes and counters ------------------------------------------------------

def _name_stacks(jaxpr, found=None, outer=""):
    """[(name stack, primitive)] of every equation, in trace order; an
    inner jaxpr's stacks are relative to the equation that holds it."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        found.append((stack, eqn.primitive.name))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _name_stacks(sub, found, stack)
    return found


def test_route_runs_under_its_scope_ahead_of_attention():
    """In the block's trace every equation of the routing stands under
    ``hvd.moe_route`` and ahead of the first one under
    ``hvd.flash_attention``; the walk stands under ``hvd.moe_ffn`` after
    it; the other families' routing stays inside ``hvd.moe_ffn``."""
    from horovod_tpu.monitor.span_audit import DEVICE_SCOPES

    assert "hvd.moe_route" in DEVICE_SCOPES
    params, toks = _params(3), _tokens(1)
    stacks = _name_stacks(jax.make_jaxpr(lambda p: _model().apply(
        {"params": p}, toks[:, :-1]))(params).jaxpr)
    for layer in ("h0", "h1"):
        mine = [(i, s) for i, (s, _) in enumerate(stacks)
                if f"/{layer}/" in s + "/"]
        route = [i for i, s in mine if "hvd.moe_route" in s]
        attn = [i for i, s in mine if "hvd.flash_attention" in s]
        walk = [i for i, s in mine if "hvd.moe_ffn" in s]
        assert route and attn and walk
        assert max(route) < min(attn) < max(attn) < min(walk)
        assert not any("hvd.moe_ffn" in s and "hvd.moe_route" in s
                       for _, s in mine)
        sorts = [i for i, (s, prim) in enumerate(stacks) if prim in (
            "sort", "top_k") and f"/{layer}/" in s + "/"]
        assert sorts and set(sorts) <= set(route)
    other = SparseMoEDecoder(dataclasses.replace(
        _model().cfg, router_input="mlp_input"))
    stacks = _name_stacks(jax.make_jaxpr(lambda p: other.apply(
        {"params": p}, toks[:, :-1]))(params).jaxpr)
    assert not any("hvd.moe_route" in s for s, _ in stacks)
    assert any("hvd.moe_ffn" in s and prim == "sort" for s, prim in stacks)


def test_counters_say_what_was_traced():
    def read():
        return {
            "relu": counter("moe.activation", kind="relu").value,
            "silu": counter("moe.activation", kind="silu").value,
            "block": counter("moe.router_input", at="block_input").value,
            "mlp": counter("moe.router_input", at="mlp_input").value,
            "group": counter("flash.kv_group").value}

    before = read()
    jax.eval_shape(lambda p: _model().apply({"params": p},
                                            _tokens(1)[:, :-1]), _params(3))
    after = read()
    moved = {k: after[k] - before[k] for k in before}
    assert moved == {"relu": 2, "silu": 0, "block": 2, "mlp": 0,
                     "group": 14}


def test_flash_counters_at_the_cells_shape():
    """One sliding call of the cell, traced and not run: 28 query heads on
    4 KV heads of 128 over 16,384 rows under a window of 4,096, in place
    over [B, T, 3584]: ``flash.kv_group`` reads 7 and the tiles carry the
    window's label; at blocks of 1,024 a q block's band is five cells."""
    from horovod_tpu.ops import flash_attention as F

    labels = dict(kernel="fwd", window="4096")
    names = ("flash.tiles_total", "flash.tiles_computed",
             "flash.tiles_masked")
    read = lambda: ([counter(n, **labels).value for n in names],
                    counter("flash.kv_group").value,
                    counter("flash.layout", path="in_place").value)
    before = read()
    q = jax.ShapeDtypeStruct((1, 16384, 28, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16)
    out = jax.eval_shape(lambda q, k, v: hvd.flash_attention(
        q, k, v, causal=True, window=4096, block_q=1024, block_k=1024),
        q, k, k)
    assert out.shape == q.shape
    after = read()
    tiles = [a - b for a, b in zip(after[0], before[0])]
    assert after[1] - before[1] == 7 and after[2] - before[2] == 1
    assert tuple(tiles) == F._tile_counts(True, True, 16, 16, 1024, 1024,
                                          window=4096)
    assert tiles[0] > tiles[1] > tiles[2] > 0
    assert F._band_blocks(4096, 1024, 16) == 5
    assert F._reads_in_place(28, 128)


# -- the configuration --------------------------------------------------------

def test_from_dict_reads_the_catalog_rows_config_verbatim():
    if os.path.exists(CATALOG_FILE):
        with open(CATALOG_FILE) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        row = next(r for r in rows
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert row["config"] == CATALOG
    cfg = SparseMoEConfig.from_dict(CATALOG)
    assert (cfg.layers, cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.vocab_size) == (
        52, 2560, 28, 4, 128, 151936)
    assert (cfg.num_experts, cfg.num_local_experts, cfg.first_local_expert,
            cfg.num_experts_per_tok, cfg.moe_intermediate_size) == (
        64, 64, 0, 6, 768)
    assert cfg.layer_types == (decoder.FULL, decoder.SLIDING,
                               decoder.SLIDING, decoder.SLIDING) * 13
    assert (cfg.sliding_window, cfg.rope_layers, cfg.rope_theta,
            cfg.rms_norm_eps) == (4096, "sliding", 1.5e6, 1e-6)
    assert (cfg.qk_norm, cfg.router_input, cfg.expert_activation,
            cfg.scoring, cfg.num_shared_experts, cfg.num_dense_layers,
            cfg.sandwich_norms, cfg.attention_gate) == (
        False, "block_input", "relu", "softmax", 0, 0, False, False)
    assert not cfg.has_router_bias() and cfg.block_length is None
    # The families it is not stay what they were.
    assert SparseMoEConfig().qk_norm and SparseMoEConfig(
    ).router_input == "mlp_input" and SparseMoEConfig(
    ).expert_activation == "silu"


def test_from_dict_takes_the_cut_and_refuses_what_is_not_built():
    cut = dict(CATALOG, layers=4, num_local_experts=16, vocab_size=37984)
    cfg = SparseMoEConfig.from_dict(cut)
    assert cfg.layer_types == (decoder.FULL,) + (decoder.SLIDING,) * 3
    assert (cfg.layers, cfg.num_local_experts, cfg.num_experts) == (4, 16, 64)
    every = SparseMoEConfig.from_dict(dict(cut, rope_layout=[1] * 52))
    assert every.rope_layers == "all"
    with pytest.raises(NotImplementedError, match="rope_layout"):
        SparseMoEConfig.from_dict(dict(cut, rope_layout=[1, 0, 1, 1] * 13))
    with pytest.raises(NotImplementedError, match="softmax"):
        SparseMoEConfig.from_dict(
            dict(cut, moe_primary_router_apply_softmax=False))
    with pytest.raises(ValueError, match="sliding_window_layout"):
        SparseMoEConfig.from_dict(dict(cut, sliding_window_layout=[0, 1]))
    with pytest.raises(ValueError, match="does not know"):
        SparseMoEConfig.from_dict({"rope_theta": 1.0, "model_type": "x"})
    with pytest.raises(ValueError, match="router_input"):
        SparseMoEConfig(router_input="attention")
    with pytest.raises(ValueError, match="expert_activation"):
        SparseMoEConfig(expert_activation="gelu")
