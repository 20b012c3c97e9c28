"""``horovod_tpu.models.SambaY`` (state-space layers, differential attention
with a window and over every key, gated memory units and cross-attention
that read what earlier layers made) against the plain reference
(benchmarks/lib/reference_sambay.py) on seeded random weights at a small
size: each mixer, the trees, the loss, every gradient leaf, an AdamW step;
what is handed on takes the SUM of its readers' gradients; the window's
edge; rematerialisation changes no gradient; the vocabulary's share."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from benchmarks.lib import manifest
from benchmarks.lib import reference_sambay as ref
from horovod_tpu.models import SambaY, SambaYConfig
from horovod_tpu.models import sambay
from horovod_tpu.monitor.registry import counter
from test_diff_attention import parent_combine, parent_lay
from test_sparse_moe_decoder import (_checkpoints, _kept_when_split,
                                     _names_saved, _one_class_for_all_layers,
                                     _saved_by_layer)

CFG = {"model_type": "phi4flash", "num_hidden_layers": 32,
       "hidden_size": 64, "num_attention_heads": 4,
       "num_key_value_heads": 2, "vocab_size": 96, "intermediate_size": 96,
       "layers": [0, 1, 16, 17, 18, 19],
       "layer_types": ["mamba", "sliding_attention", "mamba",
                       "full_attention", "gmu", "cross_attention"],
       "sliding_window": 24, "layer_norm_eps": 1e-5, "mamba_d_state": 16,
       "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 4}
# Two readers of each handed-on value: the cell's cut has one of each.
DEEP = dict(CFG, layers=[0, 1, 16, 17, 18, 19, 20, 21],
            layer_types=CFG["layer_types"] + ["gmu", "cross_attention"])
OPT = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "clip_norm": 1.0}
T = 64


def _tokens(seed, steps=1, vocab=CFG["vocab_size"]):
    return jax.random.randint(jax.random.key(seed), (steps, 1, T + 1), 0,
                              vocab)


def _model(cfg=CFG, **overrides):
    return SambaY(SambaYConfig.from_dict(cfg, dtype=jnp.float32,
                                         **overrides))


def _weights(cfg=CFG, seed=3):
    return jax.jit(functools.partial(
        ref.make_params, s=ref.sizes_from_config(cfg)))(jnp.uint32(seed))


def _program_loss(model, toks):
    def loss(p):
        logits = model.apply({"params": p}, toks[:, :-1])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.take_along_axis(logp, toks[:, 1:, None], -1).sum()
    return loss


def _close(got, want, rtol=2e-4):
    """Every leaf within ``rtol`` of the reference leaf's largest entry."""
    got, want = ref.path_dict(got), ref.path_dict(want)
    assert set(got) == set(want)
    for name, w in want.items():
        scale = max(float(jnp.abs(w).max()), 1e-12)
        gap = float(jnp.abs(got[name] - w).max()) / scale
        assert gap < rtol, (name, gap)


@pytest.fixture(scope="module")
def weights():
    return _weights()


def test_the_tree_is_the_references():
    want = jax.eval_shape(_model().init, jax.random.key(0),
                          jax.ShapeDtypeStruct((1, T), jnp.int32))["params"]
    got = jax.eval_shape(functools.partial(
        ref.make_params, s=ref.sizes_from_config(CFG)),
        jax.ShapeDtypeStruct((), jnp.uint32))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert "wq" in got["h5"]["mixer"] and "wqkv" not in got["h5"]["mixer"]
    assert set(got["h4"]["mixer"]) == {"in_proj", "out_proj"}


def test_initial_weights_follow_the_family(weights):
    mixer = weights["h0"]["mixer"]
    np.testing.assert_allclose(mixer["A_log"][7], np.log(np.arange(1, 17)),
                               rtol=1e-6)
    np.testing.assert_array_equal(mixer["D"], 1.0)
    dt = jax.nn.softplus(mixer["dt_bias"])
    assert 1e-3 * 0.999 <= float(dt.min()) and float(dt.max()) <= 0.1001
    assert 0.05 < float(weights["h1"]["mixer"]["lq1"].std()) < 0.2
    # a depthwise convolution of 4 taps keeps the framework's own draw
    for leaf in (mixer["conv_w"], mixer["conv_b"]):
        assert float(abs(leaf).max()) <= 0.5
        assert 0.25 < float(leaf.std()) < 0.33           # 0.5 / sqrt(3)
    # The program's own initialisers draw from the same families.
    own = _model().init(jax.random.key(1), jnp.zeros((1, T), jnp.int32))
    mine = own["params"]["h0"]["mixer"]
    np.testing.assert_allclose(mine["A_log"], mixer["A_log"], rtol=1e-6)
    dt = jax.nn.softplus(mine["dt_bias"])
    assert 1e-3 * 0.999 <= float(dt.min()) and float(dt.max()) <= 0.1001
    assert 0.25 < float(mine["conv_w"].std()) < 0.33
    assert float(abs(mine["conv_b"]).max()) <= 0.5


@pytest.mark.parametrize("i", range(6), ids=CFG["layer_types"])
def test_a_layer_is_the_references(weights, i):
    """Built layer ``i`` alone, on random inputs and random handed-on
    values: the block's three outputs."""
    s = ref.sizes_from_config(CFG)
    cfg = SambaYConfig.from_dict(CFG, dtype=jnp.float32, remat=False)
    keys = jax.random.split(jax.random.key(i), 4)
    x = jax.random.normal(keys[0], (T, s["d_model"]))
    m = jax.random.normal(keys[1], (T, s["d_inner"]))
    k, v = (jax.random.normal(key, (T, s["kv_heads"], s["head_dim"]))
            for key in keys[2:])
    want = ref._block(x, weights[f"h{i}"], m, (k, v), i, s,
                      ref._mm("float32"), 32)
    pairs = tuple(a.reshape(1, T, s["kv_heads"] // 2, -1) for a in (k, v))
    got = sambay._Block(cfg, i).apply({"params": weights[f"h{i}"]},
                                      x[None], m[None], pairs)
    _close(got[0][0], want[0], 1e-4)
    _close(got[1][0], want[1], 1e-4)
    for mine, theirs in zip(got[2], want[2]):
        _close(mine.reshape(theirs.shape), theirs, 1e-4)


@pytest.mark.parametrize("cfg", [CFG, DEEP], ids=["cell", "two_readers"])
def test_loss_and_every_gradient_leaf(cfg):
    weights, toks = _weights(cfg), _tokens(5)
    s = ref.sizes_from_config(cfg)
    want_l, want_g = jax.value_and_grad(functools.partial(
        ref.loss_sum, s=s, q_block=32))(weights, toks[0])
    got_l, got_g = jax.value_and_grad(_program_loss(_model(cfg), toks[0]))(
        weights)
    assert abs(float(got_l) - float(want_l)) < 1e-4 * abs(float(want_l))
    _close(got_g, want_g)
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(want_g))


def _readers_detached(monkeypatch, which: str, detached: set):
    """Stop the gradient into what is handed on at the readers numbered
    ``detached`` (in layer order): ``which`` is ``m`` or ``kv``."""
    seen = []
    if which == "m":
        real = sambay._GMU.__call__

        def call(self, u, m):
            seen.append(len(seen))
            if seen[-1] in detached:
                m = jax.lax.stop_gradient(m)
            return real(self, u, m)

        monkeypatch.setattr(sambay._GMU, "__call__", call)
    else:
        real = sambay.causal_attention

        def attend(q, k, v, **kw):
            seen.append(len(seen))
            # Calls 0 and 1 are the sliding and the full layer's own.
            if seen[-1] - 2 in detached:
                k, v = jax.lax.stop_gradient((k, v))
            return real(q, k, v, **kw)

        monkeypatch.setattr(sambay, "causal_attention", attend)


@pytest.mark.parametrize("which, leaf", [
    ("m", ("h2", "mixer", "in_proj")), ("kv", ("h3", "mixer", "wqkv"))])
def test_what_is_handed_on_takes_the_sum_of_its_readers(monkeypatch, which,
                                                        leaf):
    """With g(D) the gradient when the readers in D are cut off: the whole
    gradient is g({0}) + g({1}) - g({0, 1}), each reader's part once."""
    weights, toks = _weights(DEEP), _tokens(6)

    def grad(detached):
        with monkeypatch.context() as patch:
            _readers_detached(patch, which, detached)
            g = jax.grad(_program_loss(_model(DEEP, remat=False), toks[0]))(
                weights)
        for name in leaf:
            g = g[name]
        return g

    whole, first, second, none = (grad(d) for d in
                                  (set(), {0}, {1}, {0, 1}))
    for part in (whole - first, whole - second):    # each reader adds some
        assert float(jnp.abs(part).max()) > 1e-3 * float(jnp.abs(whole).max())
    np.testing.assert_allclose(whole, first + second - none, rtol=1e-3,
                               atol=1e-5 * float(jnp.abs(whole).max()))


def test_differential_attention_is_two_explicit_softmax_maps(weights):
    """The one 128-wide flash call against the maps written out, and the
    window's edge: a key 511 tokens back is seen, one 512 back is not."""
    cfg = dict(CFG, sliding_window=512)
    s = ref.sizes_from_config(cfg)
    T_long, at = 640, 100
    H, Hk, D = s["heads"], s["kv_heads"], s["head_dim"]
    p = weights["h1"]["mixer"]
    u = jax.random.normal(jax.random.key(0), (T_long, s["d_model"]))
    layer = sambay._DiffAttention(
        SambaYConfig.from_dict(cfg, dtype=jnp.float32), 1)

    def program(u):
        return layer.apply({"params": p}, u[None])[0][0]

    q, k, v = jnp.split(u @ p["wqkv"], (H * D, (H + Hk) * D), axis=-1)
    a = ref.diff_attention(q.reshape(-1, H, D), k.reshape(-1, Hk, D),
                           v.reshape(-1, Hk, D), p, 1, 512, s,
                           ref._mm("float32"), 128)
    got = program(u)
    _close(got, a.reshape(T_long, H * D) @ p["wo"], 1e-4)
    moved = jnp.abs(program(u.at[at].add(1.0)) - got).max(-1)
    assert float(moved[at + 511]) > 1e-6
    assert float(moved[at + 512:].max()) == 0.0
    assert float(moved[:at].max()) == 0.0


@pytest.mark.parametrize("dtype, rtol", [(jnp.float32, 2e-4),
                                         (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_loss_and_gradients_are_the_expressions_they_replaced(
        monkeypatch, weights, dtype, rtol):
    """``ops/diff_attention.py``'s two functions with their hand-written
    backwards against the expressions ``_DiffAttention`` held before them,
    differentiated by JAX: the model's loss and every gradient leaf."""
    toks = _tokens(9)[0]
    model = SambaY(SambaYConfig.from_dict(CFG, dtype=dtype))
    got_l, got_g = jax.value_and_grad(_program_loss(model, toks))(weights)

    monkeypatch.setattr(sambay._diff, "lay_in_halves", parent_lay)
    monkeypatch.setattr(sambay._diff, "diff_combine", parent_combine)
    want_l, want_g = jax.value_and_grad(_program_loss(model, toks))(weights)
    assert abs(float(got_l) - float(want_l)) < 0.5 * rtol * abs(float(want_l))
    _close(got_g, want_g, rtol)
    assert float(jnp.abs(want_g["h1"]["mixer"]["subln"]).max()) > 0


@pytest.mark.parametrize("hidden, form", [(64, "scatter"),
                                          (128, "sorted_rows_kernel")])
@pytest.mark.parametrize("dtype, rtol", [(jnp.float32, 2e-4),
                                         (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_the_lookup_with_its_own_backward_is_the_lines_it_replaced(
        monkeypatch, hidden, form, dtype, rtol):
    """``ops/embed_lookup.py`` against ``embed.astype(dtype)[tokens]``
    differentiated by JAX: the model's loss and every gradient leaf, the
    tied table's among them (the lookup's rows and the head's ``dW`` in one
    leaf); the rows counted under the form their width takes."""
    cfg = dict(CFG, hidden_size=hidden)
    toks, params = _tokens(9)[0], _weights(cfg)
    model = SambaY(SambaYConfig.from_dict(cfg, dtype=dtype))
    rows = counter("embed.grad_rows", form=form)
    before = rows.value
    got_l, got_g = jax.value_and_grad(_program_loss(model, toks))(params)
    assert rows.value - before == T

    monkeypatch.setattr(sambay, "embed_lookup",
                        lambda table, tokens, dt: table.astype(dt)[tokens])
    want_l, want_g = jax.value_and_grad(_program_loss(model, toks))(params)
    assert rows.value - before == T
    assert abs(float(got_l) - float(want_l)) < 0.5 * rtol * abs(float(want_l))
    _close(got_g, want_g, rtol)


def test_one_adamw_step(weights):
    toks = _tokens(7, steps=2)
    s = ref.sizes_from_config(CFG)
    want = jax.jit(functools.partial(
        ref.train_steps, s=s, opt=OPT, micro_rows=1, q_block=32))(
        jnp.uint32(3), toks)
    tx = optax.chain(optax.clip_by_global_norm(OPT["clip_norm"]),
                     optax.adamw(OPT["lr"], b1=OPT["b1"], b2=OPT["b2"],
                                 eps=OPT["eps"],
                                 weight_decay=OPT["weight_decay"]))
    model, p, state, losses = _model(), weights, None, []
    state = tx.init(p)
    for step in toks:
        loss, g = jax.value_and_grad(_program_loss(model, step))(p)
        g = jax.tree.map(lambda a: a / T, g)
        updates, state = tx.update(g, state, p)
        p = optax.apply_updates(p, updates)
        losses.append(float(loss) / T)
    np.testing.assert_allclose(losses, want["loss"], rtol=1e-5)
    delta = ref.leaf_norms(jax.tree.map(jnp.subtract, p, weights))
    for name, norm in want["delta_norm"].items():
        assert abs(float(delta[name]) - float(norm)) <= 2e-3 * float(norm), \
            name


def test_remat_changes_no_gradient(weights):
    toks = _tokens(8)
    on, off = (jax.grad(_program_loss(_model(remat=flag), toks[0]))(weights)
               for flag in (True, False))
    _close(on, off, 1e-6)


def test_a_rematerialised_block_runs_no_kernel_again(weights):
    """The backward's jaxpr holds each forward kernel once a layer: the
    flash forward three times, the scan's forward twice."""
    toks = _tokens(8)
    text = str(jax.make_jaxpr(jax.grad(_program_loss(_model(), toks[0])))(
        weights))
    assert text.count("name=hvd_selective_scan_fwd") == 2
    assert text.count("name=hvd_selective_scan_bwd") == 2
    assert (text.count("name=hvd_flash_fwd ") + text.count(
        "name=hvd_flash_fwd\n") + text.count("name=hvd_flash_fwd_win")) == 3


def test_the_vocabulary_share(weights):
    """The eight slices' logits side by side are the whole tied head's, and
    the loss over a slice is the whole loss restricted to it."""
    toks = _tokens(9)[0]
    h = _model(return_hidden=True).apply({"params": weights}, toks[:, :-1])
    whole = jnp.einsum("btc,vc->btv", h, weights["embed"])
    rows = CFG["vocab_size"] // 8
    slices = [jnp.einsum("btc,vc->btv", h,
                         weights["embed"][k * rows:(k + 1) * rows])
              for k in range(8)]
    np.testing.assert_allclose(jnp.concatenate(slices, -1), whole,
                               rtol=1e-5, atol=1e-6)
    labels = toks[:, 1:] % rows
    got = hvd.lm_head_loss(h, weights["embed"][:rows], labels, mode="dense")
    logp = jax.nn.log_softmax(whole[..., :rows], -1)
    want = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_hands_on_and_the_readers_counters():
    cfg = SambaYConfig.from_dict(DEEP)
    assert [cfg.hands_on(i) for i in range(8)] == [
        False, False, True, True, False, False, False, False]
    before = (counter("shared.memory_readers").value,
              counter("shared.kv_readers").value)
    jax.eval_shape(SambaY(cfg).init, jax.random.key(0),
                   jax.ShapeDtypeStruct((1, T), jnp.int32))
    assert counter("shared.memory_readers").value - before[0] == 2
    assert counter("shared.kv_readers").value - before[1] == 2


@pytest.mark.parametrize("change, message", [
    ({"layer_types": ["gmu"] + CFG["layer_types"][1:]}, "reads a mamba"),
    ({"layer_types": CFG["layer_types"][:3] + ["cross_attention"] * 3},
     "reads a full_attention"),
    ({"layer_types": CFG["layer_types"][:5]}, "layer_types"),
    ({"model_type": "afmoe"}, "phi4flash")])
def test_a_configuration_that_cannot_be_built_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        SambaYConfig.from_dict(dict(CFG, **change))


CHIP = 16_909_336_064     # a v5e's ``memory_stats()["bytes_limit"]``
MIXERS = [sambay.QKV_NAME, sambay.SSM_IN_NAME, sambay.GMU_IN_NAME]


@pytest.mark.parametrize("memory, names, hidden_layers, gb", [
    (16 * 2 ** 30, MIXERS + [sambay.MLP_HIDDEN_NAME], [4, 5], 1.3),
    (CHIP, MIXERS + [sambay.MLP_HIDDEN_NAME], [4, 5], 1.3),
    (32 * 2 ** 30, MIXERS + [sambay.MLP_HIDDEN_NAME], [0, 1, 2, 3, 4, 5],
     2.642),
    (14 * 2 ** 30, MIXERS + [sambay.MLP_HIDDEN_NAME], [5], 0.965),
    (12 * 2 ** 30, MIXERS, [], 0.629),
    # Half the device: q / k / v whole; the state-space in-projections do
    # not fit whole and, feeding no matmul, are kept by no layer.
    (8 * 2 ** 30, MIXERS[:1], [], 0.21),
    (4 * 2 ** 30, [], [], 0),
], ids=["16GiB", "the_chips", "32GiB", "14GiB", "12GiB", "8GiB", "4GiB"])
def test_the_cells_configuration_builds_and_keeps_its_projections(
        memory, names, hidden_layers, gb):
    """At the cell's size the mixers' projections are kept whole and, since
    PR 47, the MLP's hidden projections by the LAST 2 of the 6 layers
    (0.671 GB of the 0.68 left; the six would be 2.0): never more than
    ``KEEP_SHARE`` of the memory, and the next earlier layer would pass it.
    A larger or smaller device keeps more or less, by bytes alone; a
    mixer's projections by every layer that has them or by none."""
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "phi-4-mini-flash.json")) as f:
        cfg = SambaYConfig.from_dict(json.load(f))
    assert (cfg.d_inner, cfg.head_dim, cfg.mamba_dt_rank) == (5120, 64, 160)
    assert cfg.layers == (0, 1, 16, 17, 18, 19)
    kept = sambay.remat_kept(cfg, 1, 8192, memory_bytes=memory)
    candidates = sambay.remat_candidates(cfg, 1, 8192)
    assert list(kept) == names
    for name in MIXERS:
        assert name not in kept or kept[name] == candidates[name]
    hidden = kept.get(sambay.MLP_HIDDEN_NAME, (0,) * 6)
    assert [i for i, b in enumerate(hidden) if b] == hidden_layers
    assert all(hidden[i] == candidates[sambay.MLP_HIDDEN_NAME][i]
               for i in hidden_layers)
    held = sum(map(sum, kept.values()))
    assert round(held / 1e9, 3) == gb
    left = 0.125 * memory - sambay.remat_kept_anyway(cfg, 1, 8192) - held
    assert not kept or left >= 0
    if sambay.MLP_HIDDEN_NAME in kept and len(hidden_layers) < 6:
        assert candidates[sambay.MLP_HIDDEN_NAME][0] > left
    assert dataclasses.replace(cfg, remat=False).remat is False


# A candidate the budget splits, at this file's size (PR 47).

# (the first name that does not fit whole, the layers of it the budget has
# room for, the layers that keep it)
SPLITS = [(sambay.MLP_HIDDEN_NAME, 2, [4, 5]), (sambay.QKV_NAME, 2, []),
          (sambay.SSM_IN_NAME, 1, []), (sambay.MLP_HIDDEN_NAME, 5,
                                        [1, 2, 3, 4, 5])]


@pytest.fixture(scope="module")
def unrematerialised(weights):
    return jax.grad(_program_loss(_model(remat=False), _tokens(8)[0]))(
        weights)


@pytest.mark.parametrize("split, room, layers", SPLITS)
def test_a_split_candidate_is_saved_by_its_last_layers_alone(
        monkeypatch, weights, unrematerialised, split, room, layers):
    """Layer ``i``'s ``checkpoint`` saves exactly the names its entry says
    (and, to no effect, a name its kind of mixer does not hold) beside the
    kernels' outputs, ``remat.kept_layers`` counts the layers a name, a
    mixer's projections that do not fit for all are kept by none, and the
    gradients are the unrematerialised model's."""
    model, toks = _model(), _tokens(8)[0]
    kept, candidates = _kept_when_split(monkeypatch, model.cfg, split, room,
                                        layers, sambay)
    counters = {name: counter("remat.kept_layers", value=name)
                for name in candidates}
    before = {name: c.value for name, c in counters.items()}
    loss = _program_loss(model, toks)
    blocks = _checkpoints(jax.make_jaxpr(loss)(weights).jaxpr)
    assert len(blocks) == len(model.cfg.layers)
    anyway = [sambay._flash.OUT_NAME, sambay._scan.OUT_NAME]
    for i, eqn in enumerate(blocks):
        assert _names_saved(
            eqn, anyway + MIXERS + [sambay.MLP_HIDDEN_NAME]) == anyway + \
            _saved_by_layer(kept, candidates, i)
        assert (split in _names_saved(eqn, [split])) == (
            bool(layers) and i >= layers[0])
    assert {name: c.value - before[name] for name, c in counters.items()} \
        == {name: sum(b > 0 for b in kept.get(name, ()))
            for name in candidates}
    assert counters[split].value - before[split] == len(layers)
    _close(jax.grad(loss)(weights), unrematerialised, 1e-6)


def test_where_layers_keep_alike_the_lowered_text_is_the_parents(
        monkeypatch, weights):
    """Every candidate fits at this file's size, so nothing is split: the
    layers are ONE rematerialised class, as before PR 47, and the
    differentiated model lowers to the text it lowered to then."""
    model = _model()
    kept = sambay.remat_kept(model.cfg, 1, T)
    assert kept == sambay.remat_candidates(model.cfg, 1, T)
    assert len(set(sambay.rematerialised(
        sambay._Block, kept, kept, len(model.cfg.layers)))) == 1
    grad = jax.grad(_program_loss(model, _tokens(8)[0]))
    text = jax.jit(grad).lower(weights).as_text()
    monkeypatch.setattr(sambay, "rematerialised", _one_class_for_all_layers)
    assert jax.jit(grad).lower(weights).as_text() == text
