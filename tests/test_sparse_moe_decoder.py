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
ALL_SIX = (decoder.PLAN_NAME, decoder.MLP_OUT_NAME, decoder.ATTN_OUT_NAME,
           decoder.QKV_NAME, decoder.GATE_NAME, decoder.MLP_HIDDEN_NAME)


@pytest.mark.parametrize("config, seq_len, memory, want, gb", [
    ("trinity-mini", 8192, V5E, ALL_SIX, 1.429),
    ("keye-vl2-30b-a3b", 16384, V5E, ALL_SIX[:1], 0.009),
    ("trinity-mini", 32768, V5E, ALL_SIX[:1], 0.013),
    ("keye-vl2-30b-a3b", 32768, V5E, (), 0),
    ("trinity-mini", 8192, V5E // 2, ALL_SIX[:3], 0.339),
    ("trinity-mini", 8192, V5E // 8, (), 0),
    ("keye-vl2-30b-a3b", 16384, V5E // 8, (), 0),
], ids=lambda v: str(v) if isinstance(v, (str, int)) else "")
def test_the_rule_keeps_by_bytes(config, seq_len, memory, want, gb):
    """``trinity-mini``'s shape keeps all six candidates and the sparse
    cell's the plan alone: its blocks keep 1.42 GB whatever the rule says
    and its q / k / v would be 1.0 GB more (PERF.md, PR 38). Four times /
    twice the sequence, or a half / an eighth of the memory, keep the
    shorter prefix that fits ``KEEP_SHARE`` of it, down to nothing."""
    cell = _cell(config, seq_len)
    kept = decoder.remat_kept(*cell, memory_bytes=memory)
    assert tuple(kept) == want
    total = sum(sum(by_layer) for by_layer in kept.values())
    assert round(total / 1e9, 3) == gb
    held = decoder.remat_kept_anyway(*cell) + total
    assert not kept or held <= decoder.KEEP_SHARE * memory
    candidates = decoder.remat_candidates(*cell)
    if len(kept) < len(candidates):
        following = list(candidates.values())[len(kept)]
        assert held + sum(following) > decoder.KEEP_SHARE * memory


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
