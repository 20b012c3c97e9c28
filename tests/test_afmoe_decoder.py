"""``horovod_tpu.models.SparseMoEDecoder`` built from ``afmoe`` keys (window
and full grouped-KV attention, a dense layer, sigmoid-routed experts beside
a shared one, the balancing bias as state) against the plain reference
(benchmarks/lib/reference_afmoe.py) on seeded random weights at a small
size: the trees, the loss, every gradient leaf, and three AdamW steps with
the bias moving."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.lib import reference_afmoe as ref
from horovod_tpu.models import (SparseMoEConfig, SparseMoEDecoder,
                                update_router_biases)

CFG = {"model_type": "afmoe", "layers": 3, "num_hidden_layers": 8,
       "hidden_size": 64, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 96,
       "intermediate_size": 96, "moe_intermediate_size": 32,
       "layer_types": ["sliding_attention", "sliding_attention",
                       "full_attention", "sliding_attention"],
       "sliding_window": 24, "num_dense_layers": 1, "num_experts": 8,
       "num_local_experts": 4, "first_local_expert": 2,
       "num_experts_per_tok": 2, "num_shared_experts": 1,
       "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826,
       "load_balance_coeff": 0.001, "n_group": 1, "topk_group": 1,
       "mup_enabled": True, "rms_norm_eps": 1e-5, "rope_theta": 10000}
OPT = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "clip_norm": 1.0}
T = 64
SIZES = ref.sizes_from_config(CFG)


def _tokens(seed, steps=1):
    return jax.random.randint(jax.random.key(seed), (steps, 1, T + 1), 0,
                              CFG["vocab_size"])


def _model(**overrides):
    return SparseMoEDecoder(SparseMoEConfig.from_dict(
        CFG, dtype=jnp.float32, return_load=True, **overrides))


def _program_loss(model, toks):
    def loss(p, b):
        logits, loads = model.apply({"params": p, "router_bias": b},
                                    toks[:, :-1])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.take_along_axis(logp, toks[:, 1:, None], -1).sum(), loads
    return loss


@pytest.fixture(scope="module")
def weights():
    return jax.jit(functools.partial(ref.make_params, s=SIZES))(
        jnp.uint32(3))


def test_both_trees_are_the_references():
    model = SparseMoEDecoder(SparseMoEConfig.from_dict(CFG))
    want = jax.eval_shape(model.init, jax.random.key(0),
                          jax.ShapeDtypeStruct((1, T), jnp.int32))
    got = {"params": jax.eval_shape(
        functools.partial(ref.make_params, s=SIZES),
        jax.ShapeDtypeStruct((), jnp.uint32)),
        "router_bias": jax.eval_shape(lambda: ref.zero_biases(SIZES))}
    assert set(want) == {"params", "router_bias"}
    for name in got:
        assert jax.tree.structure(want[name]) == jax.tree.structure(
            got[name]), name
        for a, b in zip(jax.tree.leaves(want[name]),
                        jax.tree.leaves(got[name])):
            assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert set(got["router_bias"]) == {"h1", "h2"}     # h0 is dense
    assert "mlp" in got["params"]["h0"] and "moe" not in got["params"]["h0"]
    assert "shared" in got["params"]["h1"]["moe"]


def test_config_reads_the_published_keys():
    cfg = SparseMoEConfig.from_dict(CFG)
    assert cfg.layers == 3 and cfg.layer_types == (
        "sliding_attention", "sliding_attention", "full_attention")
    assert (cfg.sliding_window, cfg.num_dense_layers, cfg.intermediate_size,
            cfg.num_shared_experts) == (24, 1, 96, 1)
    assert (cfg.scoring, cfg.route_norm, cfg.route_scale,
            cfg.load_balance_coeff) == ("sigmoid", True, 2.826, 0.001)
    assert cfg.sandwich_norms and cfg.attention_gate
    assert cfg.rope_layers == "sliding" and cfg.embed_scale == 8.0
    assert cfg.has_router_bias()
    assert [cfg.attention_kind(i) for i in range(3)] == list(cfg.layer_types)
    with pytest.raises(NotImplementedError):
        SparseMoEConfig.from_dict({**CFG, "n_group": 2})
    with pytest.raises(ValueError):
        SparseMoEConfig.from_dict({**CFG, "model_type": "other"})


@pytest.mark.parametrize("biased", [False, True])
def test_loss_gradients_and_counts_are_the_references(weights, biased):
    """float32 at ``highest`` on both sides: the same arithmetic."""
    toks = _tokens(1)[0]
    biases = ref.zero_biases(SIZES)
    if biased:
        biases = jax.tree.map(lambda b: 0.05 * jax.random.normal(
            jax.random.key(7), b.shape), biases)
    with jax.default_matmul_precision("highest"):
        (loss, loads), grads = jax.value_and_grad(
            _program_loss(_model(), toks), has_aux=True)(weights, biases)
    (want_loss, counts), want = jax.value_and_grad(
        lambda p: ref.loss_sum(p, biases, toks, SIZES, q_block=32),
        has_aux=True)(weights)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    for name in counts:
        np.testing.assert_array_equal(np.asarray(loads[name]),
                                      np.asarray(counts[name]))
    got, want = ref.path_dict(grads), ref.path_dict(want)
    assert set(got) == set(want)
    for leaf in want:
        scale = float(jnp.abs(want[leaf]).max()) + 1e-6
        np.testing.assert_allclose(np.asarray(got[leaf]) / scale,
                                   np.asarray(want[leaf]) / scale,
                                   atol=2e-4, err_msg=leaf)


def test_three_steps_with_the_bias_moving(weights):
    """AdamW behind the clip, then ``update_router_biases``, three steps on
    three batches: losses, the parameters' change and the biases' against
    the reference's ``train_steps``; the biases move every step and keep
    summing to zero."""
    toks = _tokens(2, steps=3)
    model = _model()
    tx = optax.chain(optax.clip_by_global_norm(OPT["clip_norm"]),
                     optax.adamw(OPT["lr"], b1=OPT["b1"], b2=OPT["b2"],
                                 eps=OPT["eps"],
                                 weight_decay=OPT["weight_decay"]))
    n_tok = T

    @jax.jit
    def step(p, s, b, batch):
        with jax.default_matmul_precision("highest"):
            (loss, loads), g = jax.value_and_grad(
                _program_loss(model, batch), has_aux=True)(p, b)
        g = jax.tree.map(lambda a: a / n_tok, g)
        updates, s = tx.update(g, s, p)
        b = update_router_biases(b, loads,
                                 coeff=CFG["load_balance_coeff"])
        return optax.apply_updates(p, updates), s, b, loss / n_tok

    p, s, b = weights, tx.init(weights), ref.zero_biases(SIZES)
    losses, seen = [], []
    for batch in toks:
        p, s, b, loss = step(p, s, b, batch)
        losses.append(float(loss))
        seen.append(jax.tree.map(np.asarray, b))
    want = jax.jit(functools.partial(
        ref.train_steps, s=SIZES, opt=OPT, micro_rows=1, q_block=32))(
        jnp.uint32(3), toks)
    np.testing.assert_allclose(losses, np.asarray(want["loss"]), rtol=1e-5)
    got = {**ref.leaf_norms(jax.tree.map(jnp.subtract, p, weights)),
           **ref.leaf_norms(b)}
    assert set(got) == set(want["delta_norm"])
    for leaf, norm in want["delta_norm"].items():
        np.testing.assert_allclose(float(got[leaf]), float(norm), rtol=2e-3,
                                   err_msg=leaf)
    for before, after in zip([ref.zero_biases(SIZES)] + seen, seen):
        for layer in after:
            old = np.asarray(before[layer]["moe"]["bias"])
            new = after[layer]["moe"]["bias"]
            assert np.abs(new - old).max() > 5e-4, layer
            assert abs(float(new.sum())) < 1e-6, layer


def test_the_bias_moves_the_choices():
    """State the step carries changes what the next step computes: a
    bias favouring other experts moves the counts and the loss."""
    weights = ref.make_params(jnp.uint32(4), SIZES)
    toks = _tokens(5)[0]
    loss = _program_loss(_model(), toks)
    zero = ref.zero_biases(SIZES)
    tilted = jax.tree.map(lambda b: b.at[0].set(1.0), zero)
    (l0, n0), (l1, n1) = loss(weights, zero), loss(weights, tilted)
    assert float(n1["h1"][0]) == T and float(n0["h1"][0]) < T
    assert float(l0) != float(l1)


@pytest.mark.parametrize("fault, override", [
    ("a full layer given the window", dict(layer_types=(
        "sliding_attention",) * 3)),
    ("rotary position on the full layer", dict(rope_layers="all")),
    ("no output gate", dict(attention_gate=False)),
    ("no sandwich norms", dict(sandwich_norms=False)),
    ("an unscaled embedding", dict(embed_scale=1.0)),
    ("the gates unscaled", dict(route_scale=1.0))])
def test_each_departure_from_the_equations_shows(weights, fault, override):
    """Each of the family's particulars is in the loss: a model built
    without it does not read the reference's loss."""
    toks = _tokens(1)[0]
    biases = ref.zero_biases(SIZES)
    want, _ = ref.loss_sum(weights, biases, toks, SIZES, q_block=32)
    model = _model(**override)
    p = weights
    if not model.cfg.attention_gate or not model.cfg.sandwich_norms:
        shapes = jax.eval_shape(model.init, jax.random.key(0),
                                jnp.zeros((1, T), jnp.int32))["params"]
        p = jax.tree.map(lambda _, leaf: leaf, shapes, jax.tree.map(
            lambda x: x, {k: _pruned(weights[k], shapes[k])
                          for k in shapes}))
    with jax.default_matmul_precision("highest"):
        got, _ = _program_loss(model, toks)(p, biases)
    # The float32 program reads the reference to 2e-6 of it.
    assert abs(float(got) - float(want)) > 1e-4 * float(want), fault


def _pruned(tree, like):
    """``tree`` cut down to the leaves ``like`` has."""
    if isinstance(like, dict):
        return {k: _pruned(tree[k], like[k]) for k in like}
    return tree
