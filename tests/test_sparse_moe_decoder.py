"""``horovod_tpu.models.SparseMoEDecoder`` against the plain reference
(benchmarks/lib/reference_sparse_moe.py) on seeded random weights at a
small size: loss, every gradient leaf and one AdamW step."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.lib import reference_afmoe as afref
from benchmarks.lib import reference_sparse_moe as ref
from horovod_tpu.models import SparseMoEConfig, SparseMoEDecoder
from horovod_tpu.models import sparse_moe_decoder as decoder
from test_afmoe_decoder import CFG as AFMOE

CFG = {"layers": 2, "num_hidden_layers": 2, "hidden_size": 64,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "vocab_size": 96, "num_experts": 8, "num_local_experts": 4,
       "first_local_expert": 2, "num_experts_per_tok": 2,
       "moe_intermediate_size": 32, "rms_norm_eps": 1e-6,
       "rope_theta": 10000000,
       "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                     "indexer_num_kv_heads": 1, "topk": 16}}
OPT = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "clip_norm": 1.0}
T = 64
SIZES = ref.sizes_from_config(CFG)


def _tokens(seed):
    return jax.random.randint(jax.random.key(seed), (1, T + 1), 0,
                              CFG["vocab_size"])


def _program_loss(model, toks):
    def loss(p):
        logits = model.apply({"params": p}, toks[:, :-1])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.take_along_axis(logp, toks[:, 1:, None], -1).sum()
    return loss


@pytest.fixture(scope="module")
def float32_pair():
    """(params, tokens, program (loss, grads), reference (loss, grads))
    with the program in float32 at ``highest``: the same arithmetic."""
    params = jax.jit(functools.partial(ref.make_params, s=SIZES))(
        jnp.uint32(3))
    toks = _tokens(1)
    model = SparseMoEDecoder(SparseMoEConfig.from_dict(
        CFG, dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(_program_loss(model, toks))(params)
    want = jax.value_and_grad(
        lambda p: ref.loss_sum(p, toks, SIZES, q_block=32))(params)
    return params, toks, got, want


def test_parameter_tree_is_the_references():
    model = SparseMoEDecoder(SparseMoEConfig.from_dict(CFG))
    want = jax.eval_shape(model.init, jax.random.key(0),
                          jax.ShapeDtypeStruct((1, T), jnp.int32))["params"]
    got = jax.eval_shape(functools.partial(ref.make_params, s=SIZES),
                         jax.ShapeDtypeStruct((), jnp.uint32))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_config_reads_the_published_keys():
    cfg = SparseMoEConfig.from_dict(CFG)
    assert (cfg.layers, cfg.topk, cfg.indexer_num_heads,
            cfg.indexer_head_dim) == (2, 16, 2, 8)
    assert (cfg.num_experts, cfg.num_local_experts,
            cfg.first_local_expert) == (8, 4, 2)
    assert SparseMoEConfig.from_dict(
        {k: v for k, v in CFG.items() if k != "layers"}).layers == 2


def test_loss_is_the_references(float32_pair):
    """(a): float32 against float32: 1e-6 relative (sums in another
    order)."""
    _, _, (loss, _), (want, _) = float32_pair
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)


@pytest.mark.parametrize("leaf", sorted(ref.path_dict(jax.eval_shape(
    functools.partial(ref.make_params, s=SIZES),
    jax.ShapeDtypeStruct((), jnp.uint32)))))
def test_gradient_leaf_is_the_references(float32_pair, leaf):
    """(a) every gradient leaf, to 1e-5 of the leaf's largest entry
    (float32 rounding through two layers); (d) the indexer's three
    weights get exactly zero, in the program as in the reference."""
    _, _, (_, got), (_, want) = float32_pair
    a, b = ref.path_dict(got)[leaf], ref.path_dict(want)[leaf]
    if "/indexer/" in leaf:
        assert not np.asarray(a).any() and not np.asarray(b).any()
        return
    assert float(jnp.abs(b).max()) > 0
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=1e-5 * float(jnp.abs(b).max()))


def test_one_adamw_step_is_the_references(float32_pair):
    """(a): clip + AdamW through optax on the program's gradient lands
    where the reference's written-out step lands: the worst leaf's change
    agrees to 1e-4 of its norm (Adam divides by sqrt(v): a gradient entry
    near zero turns its rounding into a step of up to lr)."""
    params, toks, (_, grads), _ = float32_pair
    n_tok = toks.shape[1] - 1
    tx = optax.chain(optax.clip_by_global_norm(OPT["clip_norm"]), optax.adamw(
        OPT["lr"], b1=OPT["b1"], b2=OPT["b2"], eps=OPT["eps"],
        weight_decay=OPT["weight_decay"]))
    g = jax.tree.map(lambda a: a / n_tok, grads)
    updates, _ = tx.update(g, tx.init(params), params)
    got = ref.leaf_norms(updates)
    out = jax.jit(functools.partial(
        ref.train_steps, s=SIZES, opt=OPT, micro_rows=1, q_block=32))(
        jnp.uint32(3), toks[None])
    np.testing.assert_allclose(
        float(out["loss"][0]), float(float32_pair[2][0]) / n_tok, rtol=1e-6)
    for leaf, want in out["delta_norm"].items():
        # atol: the reference subtracts two float32 weight trees, so a
        # change of lr * wd * w (an indexer leaf's, 7e-6) carries the
        # weights' own rounding
        np.testing.assert_allclose(float(got[leaf]), float(want), rtol=1e-4,
                                   atol=5e-9, err_msg=leaf)


def test_bfloat16_model_tracks_the_reference():
    """The model as the benchmark runs it (bf16 activations): the loss
    within 2e-3 of the float32 reference's (per token), no leaf's gradient
    norm further than 6% of max(leaf, median leaf): selection and routing
    flip near their thresholds at this size, one key is 1/16 of a row."""
    params = jax.jit(functools.partial(ref.make_params, s=SIZES))(
        jnp.uint32(5))
    toks = _tokens(2)
    model = SparseMoEDecoder(SparseMoEConfig.from_dict(CFG))
    loss, grads = jax.value_and_grad(_program_loss(model, toks))(params)
    want, wgrads = jax.value_and_grad(
        lambda p: ref.loss_sum(p, toks, SIZES, q_block=32))(params)
    assert abs(float(loss) - float(want)) / T < 2e-3
    got, ref_norms = ref.leaf_norms(grads), ref.leaf_norms(wgrads)
    floor = float(np.median([float(v) for v in ref_norms.values()]))
    for leaf, n in ref_norms.items():
        gap = abs(float(got[leaf]) - float(n)) / max(float(n), floor)
        assert gap < 0.06, (leaf, gap)


def _kernel_calls(jaxpr, counts=None):
    """name -> number of ``pallas_call`` equations in ``jaxpr`` and every
    jaxpr nested in it."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            counts[name] = counts.get(name, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, counts)
    return counts


@pytest.mark.parametrize("backward", ["fused", "split"])
@pytest.mark.parametrize("kept,index_calls", [
    ("the_models_policy", CFG["layers"]),
    ("the_attention_output_alone", 2 * CFG["layers"]),
])
def test_recomputed_forward_holds_no_index_kernel(monkeypatch, kept,
                                                  index_calls, backward):
    """The blocks keep the packed selection beside the attention kernel's
    output, so the differentiated model selects once a layer: what feeds
    only a saved name is dropped from the recomputed forward. With the
    selection's name out of the policy (the model of before) every layer
    selects twice. The attention kernels run once a layer either way: the
    backward as ONE ``hvd_sparse_attn_bwd`` equation a layer and none
    named ``_dq`` / ``_dkv``, or, where a KV head's dk and dv would not
    fit in VMEM, as those two."""
    import horovod_tpu.models.sparse_moe_decoder as module
    from horovod_tpu.ops import sparse_attention as sa

    if kept == "the_attention_output_alone":
        monkeypatch.setattr(module, "SELECTION_NAME", "kept_by_no_value")
    if backward == "split":
        monkeypatch.setattr(sa, "_FUSED_BWD_BUDGET", 0)
    params = jax.eval_shape(functools.partial(ref.make_params, s=SIZES),
                            jax.ShapeDtypeStruct((), jnp.uint32))
    model = SparseMoEDecoder(SparseMoEConfig.from_dict(CFG))
    calls = _kernel_calls(jax.make_jaxpr(jax.grad(
        _program_loss(model, _tokens(4))))(params).jaxpr)
    bwd = (("hvd_sparse_attn_bwd",) if backward == "fused" else
           ("hvd_sparse_attn_bwd_dq", "hvd_sparse_attn_bwd_dkv"))
    assert calls == {"hvd_index_select": index_calls,
                     "hvd_sparse_attn_fwd": CFG["layers"],
                     **{name: CFG["layers"] for name in bwd}}


def test_return_hidden_feeds_the_untied_head():
    import horovod_tpu as hvd

    params = jax.jit(functools.partial(ref.make_params, s=SIZES))(
        jnp.uint32(6))
    toks = _tokens(3)
    cfg = SparseMoEConfig.from_dict(CFG, dtype=jnp.float32)
    logits = SparseMoEDecoder(cfg).apply({"params": params}, toks[:, :-1])
    hidden = SparseMoEDecoder(SparseMoEConfig.from_dict(
        CFG, dtype=jnp.float32, return_hidden=True)).apply(
        {"params": params}, toks[:, :-1])
    assert hidden.shape == (1, T, CFG["hidden_size"])
    loss = hvd.lm_head_loss(hidden, params["head"], toks[:, 1:]).mean()
    logp = jax.nn.log_softmax(logits, -1)
    want = -jnp.take_along_axis(logp, toks[:, 1:, None], -1).mean()
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-4)


# ---------------------------------------------------------------------------
# What a rematerialised block keeps (models/sparse_moe_decoder.py
# ``remat_kept``): both families at this file's size.
# ---------------------------------------------------------------------------

FAMILIES = {"sparse": (CFG, ref), "afmoe": (AFMOE, afref)}
THE_THREE_NAMES_OF_BEFORE = "the_three_names_of_before"


def _family(family, seed=None, **overrides):
    """(model, loss of the parameters, parameters: abstract without a
    ``seed``) of a family at the tests' size; the ``afmoe`` model is applied
    with its zero router biases."""
    cfg, lib = FAMILIES[family]
    sizes = lib.sizes_from_config(cfg)
    make = functools.partial(lib.make_params, s=sizes)
    params = (jax.eval_shape(make, jax.ShapeDtypeStruct((), jnp.uint32))
              if seed is None else jax.jit(make)(jnp.uint32(seed)))
    model = SparseMoEDecoder(SparseMoEConfig.from_dict(cfg, **overrides))
    state = ({} if family == "sparse"
             else {"router_bias": afref.zero_biases(sizes)})
    toks = _tokens(7)

    def loss(p):
        logits = model.apply({"params": p, **state}, toks[:, :-1])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.take_along_axis(logp, toks[:, 1:, None], -1).sum()
    return model, loss, params


def _keep(monkeypatch, kept):
    if kept == THE_THREE_NAMES_OF_BEFORE:
        monkeypatch.setattr(decoder, "remat_kept", lambda *a, **k: {})


def _grouped_matmuls(jaxpr):
    return sum(eqn.primitive.name.startswith("ragged_dot") + sum(
        _grouped_matmuls(sub) for sub in jax.core.jaxprs_in_params(
            eqn.params)) for eqn in jaxpr.eqns)


def _recomputed(jaxpr, inside=False, found=None):
    """Primitive name -> equations inside the ``checkpoint`` equations of
    ``jaxpr`` (a backward pass: the recomputed forward and the transposes
    beside it), a loop's body left out; a ``while`` counts as ``walk_fwd``
    / ``walk_bwd`` by the 3 or 5 grouped matmuls of its body."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if name == "remat2":
            name = "checkpoint"
        if name == "while":
            name = {3: "walk_fwd", 5: "walk_bwd"}.get(
                sum(map(_grouped_matmuls, subs)), name)
            subs = []
        if inside:
            found[name] = found.get(name, 0) + 1
        for sub in subs:
            _recomputed(sub, inside or name == "checkpoint", found)
    return found


@pytest.mark.parametrize("kept", ["the_rule", THE_THREE_NAMES_OF_BEFORE])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_recomputed_forward_walks_no_expert_and_sorts_nothing(
        monkeypatch, family, kept):
    """Under the rule a block's recomputed forward holds no walk of the
    experts, no top-k, no gather of the chosen scores and no sort: the plan
    is kept, and under sandwich norms (``afmoe``) the mixture's output,
    which the post-norm's backward reads. The backward's own walk stays,
    once a routed layer. With the three names of before, the forward walk,
    the top-k, the sort and the sigmoid router's gather (the softmax
    router's scores are the top-k's own) are all there a second time."""
    _keep(monkeypatch, kept)
    model, loss, params = _family(family)
    routed = model.cfg.layers - model.cfg.num_dense_layers
    found = _recomputed(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    again = 0 if kept == "the_rule" else routed
    assert found["walk_bwd"] == routed
    # Without sandwich norms nothing reads the mixture's output and the
    # walk was never run again (the sparse family).
    assert {name: found.get(name, 0) for name in
            ("walk_fwd", "top_k", "sort", "gather", "while")} == {
        "walk_fwd": again * model.cfg.sandwich_norms, "top_k": again,
        "sort": again, "gather": again * (model.cfg.scoring == "sigmoid"),
        "while": 0}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_kept_values_leave_loss_and_gradients_equal(monkeypatch, family,
                                                    dtype):
    """A kept value is the value the recomputation would have made: the
    jitted loss and EVERY gradient leaf under the rule are bit for bit
    those under the three names of before."""
    def run():
        _, loss, params = _family(family, seed=11, dtype=jnp.dtype(dtype))
        return jax.jit(jax.value_and_grad(loss)).lower(params).compile(
            compiler_options={"xla_allow_excess_precision": False})(params)

    loss, grads = run()
    _keep(monkeypatch, THE_THREE_NAMES_OF_BEFORE)
    want_loss, want = run()
    assert float(loss) == float(want_loss)
    got, want = ref.path_dict(grads), ref.path_dict(want)
    assert set(got) == set(want)
    differing = [leaf for leaf in want if not np.array_equal(
        np.asarray(got[leaf]), np.asarray(want[leaf]))]
    assert differing == []


def _cell(name, seq_len):
    import json
    import os

    from benchmarks.lib import manifest

    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        return SparseMoEConfig.from_dict(json.load(f)), 1, seq_len


V5E = decoder.ASSUMED_MEMORY_BYTES
CHIP = 16_909_336_064     # a v5e's ``memory_stats()["bytes_limit"]``
ALL_SIX = (decoder.PLAN_NAME, decoder.MLP_OUT_NAME, decoder.ATTN_OUT_NAME,
           decoder.QKV_NAME, decoder.GATE_NAME, decoder.MLP_HIDDEN_NAME)


def _parents_rule(candidates, anyway, memory):
    """``kept_within`` as it stood before PR 47: a candidate for all its
    layers or for none, and what is left of the budget for nothing."""
    kept, total = {}, anyway
    for name, by_layer in candidates.items():
        total += sum(by_layer)
        if total > decoder.KEEP_SHARE * memory:
            break
        kept[name] = by_layer
    return kept


def _holds_the_rule(candidates, anyway, memory, kept):
    """``kept`` is the rule's answer, in the rule's own terms; returns (the
    names kept for every layer, the layers that keep the first name that
    did not fit whole, None where every candidate fits)."""
    left = decoder.KEEP_SHARE * memory - anyway - sum(
        map(sum, kept.values()))
    assert not kept or left >= 0              # never more than the share
    whole = list(_parents_rule(candidates, anyway, memory))
    assert [n for n in kept if kept[n] == candidates[n]] == whole
    if len(whole) == len(candidates):
        assert list(kept) == whole
        return whole, None
    split = list(candidates)[len(whole)]
    if split not in decoder.FEEDS_A_MATMUL:
        assert list(kept) == whole       # all its layers or none: none
        return whole, []
    assert list(kept) in (whole, whole + [split])      # nothing after it
    by_layer = candidates[split]
    got = kept.get(split, (0,) * len(by_layer))
    layers = [i for i, b in enumerate(got) if b]
    first = min(layers, default=len(got))
    # A run of LAST layers, each at the candidate's bytes ...
    assert got == (0,) * first + tuple(by_layer[first:])
    # ... and the next earlier layer that holds the name does not fit.
    assert [b for b in by_layer[:first] if b][-1] > left
    return whole, layers


GIB = 2 ** 30


@pytest.mark.parametrize("config, seq_len, memory, whole, layers, gb", [
    ("trinity-mini", 8192, V5E, ALL_SIX, None, 1.429),
    ("keye-vl2-30b-a3b", 16384, V5E, ALL_SIX[:1], [], 0.009),
    ("trinity-mini", 32768, V5E, ALL_SIX[:1], [], 0.013),
    ("keye-vl2-30b-a3b", 32768, V5E, (), [], 0),
    ("trinity-mini", 8192, V5E // 2, ALL_SIX[:3], [], 0.339),
    ("trinity-mini", 8192, V5E // 8, (), [], 0),
    ("keye-vl2-30b-a3b", 16384, V5E // 8, (), [], 0),
    ("keye-vl2-30b-a3b", 16384, CHIP, ALL_SIX[:1], [], 0.009),
    ("sdar-30b-a3b", 16384, V5E, ALL_SIX[:1], [], 0.009),
    ("sdar-30b-a3b", 16384, CHIP, ALL_SIX[:1], [], 0.009),
    ("smallthinker-21b-a3b", 16384, V5E,
     (decoder.PLAN_NAME, decoder.QKV_NAME), None, 0.609),
    ("smallthinker-21b-a3b", 32768, V5E, ALL_SIX[:1], [], 0.009),
    ("trinity-mini", 16384, V5E, ALL_SIX[:3], [], 0.677),
    ("trinity-mini", 8192, V5E // 4, ALL_SIX[:1], [], 0.003),
    # A device on which the hidden projections are the first that do not
    # fit whole: the dense layer's, six times a routed layer's shared
    # expert's, is the last the budget reaches.
    ("trinity-mini", 8192, 13 * GIB, ALL_SIX[:5], [1, 2, 3, 4], 1.228),
    ("trinity-mini", 8192, int(12.6 * GIB), ALL_SIX[:5], [3, 4], 1.161),
    ("trinity-mini", 8192, 12 * GIB, ALL_SIX[:5], [], 1.094),
    # Twice the device keeps the two cells' q / k / v for every layer.
    ("keye-vl2-30b-a3b", 16384, 2 * V5E,
     (decoder.PLAN_NAME, decoder.QKV_NAME), None, 1.016),
    ("sdar-30b-a3b", 16384, 2 * V5E,
     (decoder.PLAN_NAME, decoder.QKV_NAME), None, 1.016),
], ids=lambda v: str(v) if isinstance(v, (str, int)) else "")
def test_the_rule_keeps_by_bytes(config, seq_len, memory, whole, layers, gb):
    """``trinity-mini``'s shape keeps all six candidates whole and
    ``smallthinker-21b-a3b``'s both of its own. The sparse cell's and the
    block-diffusion cell's (whose rule sees ``2 L`` rows) keep the plan and
    none of their q / k / v, which do not fit for every layer and are not a
    name that some layers may keep alone (``FEEDS_A_MATMUL``; PERF.md, PR
    47, has what the last 4 and 5 layers' cost on the chip). Four times /
    twice the sequence, or a part of the memory, keep the shorter prefix
    that fits ``KEEP_SHARE`` of it and, where the next name is the hidden
    projections, the last layers of it that fit what is left, down to
    nothing. Never more than the share; the next earlier layer would pass
    it; nothing after the first name that did not fit whole."""
    cell = _cell(config, seq_len)
    kept = decoder.remat_kept(*cell, memory_bytes=memory)
    candidates = decoder.remat_candidates(*cell)
    got = _holds_the_rule(candidates, decoder.remat_kept_anyway(*cell),
                          memory, kept)
    assert got == (list(whole), layers)
    assert round(sum(map(sum, kept.values())) / 1e9, 3) == gb


@pytest.mark.parametrize("memory", [V5E, CHIP], ids=["16GiB", "the_chips"])
@pytest.mark.parametrize("config, seq_len", [
    ("trinity-mini", 8192), ("smallthinker-21b-a3b", 16384),
    ("keye-vl2-30b-a3b", 16384), ("sdar-30b-a3b", 16384)])
def test_where_no_hidden_projection_is_split_the_dict_is_the_parents(
        config, seq_len, memory):
    """Every candidate fits (``trinity-mini``, ``smallthinker-21b-a3b``) or
    the first that does not is q / k / v (the sparse and the
    block-diffusion cell): what PR 47's parent kept, name for name."""
    cell = _cell(config, seq_len)
    candidates = decoder.remat_candidates(*cell)
    kept = decoder.remat_kept(*cell, memory_bytes=memory)
    assert kept == _parents_rule(
        candidates, decoder.remat_kept_anyway(*cell), memory)
    assert all(kept[name] == candidates[name] for name in kept)


P, Q, H = decoder.PLAN_NAME, decoder.QKV_NAME, decoder.MLP_HIDDEN_NAME


@pytest.mark.parametrize("candidates, anyway, budget, want", [
    # Whole while they fit; the first that does not, from the last layer.
    ({P: (4, 4, 4), H: (3, 3, 3), Q: (1, 1, 1)}, 10, 29,
     {P: (4, 4, 4), H: (0, 3, 3)}),
    # Nothing after the name that was split, though it would fit.
    ({P: (4, 4, 4), H: (3, 3, 3), Q: (1, 1, 1)}, 10, 24, {P: (4, 4, 4)}),
    # A layer that does not hold the name costs nothing and ends no run.
    ({H: (5, 0, 5, 0, 5, 0)}, 0, 10, {H: (0, 0, 5, 0, 5, 0)}),
    ({H: (5, 0, 5, 0, 5, 0)}, 0, 4, {}),
    # The run is of LAST layers: an early one that would fit is not taken.
    ({H: (1, 9, 2)}, 0, 3, {H: (0, 0, 2)}),
    # What is kept anyway is counted in; over the budget keeps nothing.
    ({H: (1, 1)}, 9, 8, {}),
    ({H: (1, 1)}, 7, 8, {H: (0, 1)}),
    ({H: (1, 1)}, 6, 8, {H: (1, 1)}),
    # A name that feeds no matmul is kept by every layer or by none, and
    # the walk ends at it all the same.
    ({P: (4, 4, 4), Q: (3, 3, 3), H: (1, 1, 1)}, 10, 29, {P: (4, 4, 4)}),
    ({P: (4, 4, 4), Q: (3, 3, 3), H: (1, 1, 1)}, 10, 31,
     {P: (4, 4, 4), Q: (3, 3, 3)}),
    ({Q: (1, 1)}, 7, 8, {}),
    ({P: (0, 2, 2)}, 0, 3, {}),
])
def test_kept_within_spends_what_is_left_on_the_last_layers(
        candidates, anyway, budget, want):
    memory = budget / decoder.KEEP_SHARE
    kept = decoder.kept_within(candidates, anyway, memory)
    assert kept == want and list(kept) == list(want)
    _holds_the_rule(candidates, anyway, memory, kept)


@pytest.mark.parametrize("reported, memory", [
    (None, V5E), ({}, V5E), ({"bytes_limit": V5E // 8}, V5E // 8)],
    ids=["no_stats", "no_limit", "an_eighth"])
def test_the_rule_reads_the_devices_memory_once(monkeypatch, reported,
                                                memory):
    """A device that reports no memory (the CPU's ``memory_stats()`` is
    None) gives the 16 GB answer; one that does is believed, and asked
    once."""
    asked = []

    class Device:
        def memory_stats(self):
            asked.append(1)
            return reported

    monkeypatch.setattr(jax, "local_devices", lambda: [Device()])
    decoder.device_memory_bytes.cache_clear()
    try:
        cell = _cell("trinity-mini", 8192)
        assert decoder.remat_kept(*cell) == decoder.remat_kept(
            *cell, memory_bytes=memory)
        assert decoder.device_memory_bytes() == memory and asked == [1]
    finally:
        decoder.device_memory_bytes.cache_clear()


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_kept_bytes_are_counted_once_a_block(family):
    """``remat.kept_bytes`` by value sums to the rule's bytes over a trace's
    blocks and ``remat.kept_names`` to the names a block; what the policy
    names is in the traced program under that name."""
    from horovod_tpu.monitor.registry import counter

    model, loss, params = _family(family)
    kept = decoder.remat_kept(model.cfg, 1, T)
    assert len(kept) == {"sparse": 2, "afmoe": 6}[family]   # all, at T = 64
    counters = {name: counter("remat.kept_bytes", value=name)
                for name in kept}
    names = counter("remat.kept_names")
    before = {name: c.value for name, c in counters.items()}, names.value
    text = str(jax.make_jaxpr(loss)(params))
    assert {name: c.value - before[0][name]
            for name, c in counters.items()} == {
        name: sum(by_layer) for name, by_layer in kept.items()}
    assert names.value - before[1] == len(kept) * model.cfg.layers
    for name in kept:
        assert f"name={name}" in text


# ---------------------------------------------------------------------------
# A candidate the budget splits (PR 47): the last layers keep it, the first
# make it again.
# ---------------------------------------------------------------------------

def _memory_that_splits(cfg, split, layers_kept, family=decoder):
    """The device memory at which ``family``'s rule keeps ``split`` for the
    last ``layers_kept`` of the layers that hold it at this file's size, to
    the byte."""
    candidates = family.remat_candidates(cfg, 1, T)
    names = list(candidates)
    holding = [b for b in candidates[split] if b]
    budget = (family.remat_kept_anyway(cfg, 1, T)
              + sum(sum(candidates[n]) for n in names[:names.index(split)])
              + sum(holding[-layers_kept:]))
    return int(budget / decoder.KEEP_SHARE)


def _checkpoints(jaxpr, found=None):
    """The ``checkpoint`` equations of a forward pass, in the layers'
    order."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "remat2":
            found.append(eqn)
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _checkpoints(sub, found)
    return found


@functools.lru_cache(maxsize=None)
def _name_primitive():
    from jax.ad_checkpoint import checkpoint_name

    return jax.make_jaxpr(lambda x: checkpoint_name(x, "n"))(
        1.0).eqns[0].primitive


def _names_saved(eqn, names):
    """Of ``names``, those the ``checkpoint`` equation's policy saves."""
    return [n for n in names
            if eqn.params["policy"](_name_primitive(), name=n)]


# (family, the first name that does not fit whole, the layers of it the
# budget has room for, the layers that keep it)
SPLITS = [("sparse", decoder.QKV_NAME, 1, []),
          ("afmoe", decoder.ATTN_OUT_NAME, 1, []),
          ("afmoe", decoder.QKV_NAME, 2, []),
          ("afmoe", decoder.MLP_HIDDEN_NAME, 1, [2]),
          ("afmoe", decoder.MLP_HIDDEN_NAME, 2, [1, 2])]


def _kept_when_split(monkeypatch, cfg, split, room, layers, family=decoder):
    """``family``'s dict at this file's size on a device with room for the
    last ``room`` layers' ``split``, which ``layers`` keep."""
    monkeypatch.setattr(decoder, "device_memory_bytes", lambda: (
        _memory_that_splits(cfg, split, room, family)))
    kept = family.remat_kept(cfg, 1, T)
    candidates = family.remat_candidates(cfg, 1, T)
    before = list(candidates)[:list(candidates).index(split)]
    assert list(kept) == before + [split] * bool(layers)
    assert [i for i, b in enumerate(kept.get(split, ())) if b] == layers
    return kept, candidates


def _saved_by_layer(kept, candidates, i):
    """The kept names layer ``i`` is rematerialised under: every one but a
    name the layer holds and does not keep."""
    return [name for name in kept if kept[name][i] == candidates[name][i]]


@pytest.mark.parametrize("family, split, room, layers", SPLITS)
def test_a_split_candidate_is_saved_by_its_last_layers_alone(
        monkeypatch, family, split, room, layers):
    """Layer ``i``'s ``checkpoint`` saves exactly the names its entry says
    (and, to no effect, a name the layer does not hold), beside what a
    block keeps anyway; ``remat.kept_layers`` counts the layers a name; q /
    k / v and the attention's output, which feed no matmul, are kept by no
    layer where they do not fit for all."""
    from horovod_tpu.monitor.registry import counter

    model, loss, params = _family(family)
    kept, candidates = _kept_when_split(monkeypatch, model.cfg, split, room,
                                        layers)
    counters = {name: counter("remat.kept_layers", value=name)
                for name in candidates}
    before = {name: c.value for name, c in counters.items()}
    blocks = _checkpoints(jax.make_jaxpr(loss)(params).jaxpr)
    assert len(blocks) == model.cfg.layers
    anyway = [decoder.OUT_NAME, decoder.SELECTION_NAME,
              decoder._flash.OUT_NAME]
    for i, eqn in enumerate(blocks):
        assert _names_saved(eqn, anyway + list(ALL_SIX)) == anyway + \
            _saved_by_layer(kept, candidates, i)
        assert (split in _names_saved(eqn, [split])) == (i in layers)
    assert {name: c.value - before[name] for name, c in counters.items()} \
        == {name: sum(b > 0 for b in kept.get(name, ()))
            for name in candidates}
    assert counters[split].value - before[split] == len(layers)


@pytest.mark.parametrize("family, split, room, layers", SPLITS[2:])
def test_a_split_candidate_leaves_loss_and_gradients_equal(
        monkeypatch, family, split, room, layers):
    """Bit for bit the loss and every gradient leaf of the model that keeps
    the three names of before, as where every layer keeps the same."""
    def run():
        _, loss, params = _family(family, seed=11)
        return jax.jit(jax.value_and_grad(loss)).lower(params).compile(
            compiler_options={"xla_allow_excess_precision": False})(params)

    model, _, _ = _family(family)
    _kept_when_split(monkeypatch, model.cfg, split, room, layers)
    loss, grads = run()
    _keep(monkeypatch, THE_THREE_NAMES_OF_BEFORE)
    want_loss, want = run()
    assert float(loss) == float(want_loss)
    got, want = ref.path_dict(grads), ref.path_dict(want)
    assert [leaf for leaf in want if not np.array_equal(
        np.asarray(got[leaf]), np.asarray(want[leaf]))] == []


def _one_class_for_all_layers(block, kept, candidates, layers, *anyway):
    """The blocks as the model made them before PR 47: ONE rematerialised
    class under every kept name."""
    import flax.linen as nn

    return [nn.remat(
        block, policy=jax.checkpoint_policies.save_only_these_names(
            *anyway, *kept))] * layers


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_where_layers_keep_alike_the_lowered_text_is_the_parents(
        monkeypatch, family):
    """Every candidate fits at this file's size, so nothing is split: the
    layers are ONE rematerialised class, as before PR 47, and the
    differentiated model lowers to the text it lowered to then."""
    model, loss, params = _family(family)
    kept = decoder.remat_kept(model.cfg, 1, T)
    assert kept == decoder.remat_candidates(model.cfg, 1, T)
    assert len(set(decoder.rematerialised(
        decoder._Block, kept, kept, model.cfg.layers))) == 1
    text = jax.jit(jax.grad(loss)).lower(params).as_text()
    monkeypatch.setattr(decoder, "rematerialised", _one_class_for_all_layers)
    assert jax.jit(jax.grad(loss)).lower(params).as_text() == text


@pytest.mark.parametrize("kept, classes", [
    ({}, [0, 0, 0]),
    ({P: (0, 2, 2), Q: (3, 3, 3)}, [0, 0, 0]),
    ({P: (0, 2, 2), H: (0, 3, 3)}, [1, 0, 0]),
    ({P: (0, 2, 2), H: (0, 0, 3)}, [1, 1, 0]),
    ({H: (0, 0, 3)}, [1, 1, 0]),
])
def test_one_class_unless_a_name_is_split_and_then_two(kept, classes):
    """A layer that does not hold a name (the dense layer has no plan) is
    of the one class all the same; the layers before a split name's run
    are of a second, which does not save it."""
    candidates = {P: (0, 2, 2), Q: (3, 3, 3), H: (3, 3, 3)}
    blocks = decoder.rematerialised(decoder._Block, kept, candidates, 3)
    # The last layer is of the class under every kept name.
    assert [int(b is not blocks[-1]) for b in blocks] == classes
    assert len(set(blocks)) == len(set(classes))
