"""``hvd.moe_ffn_dropless``: the experts a chip holds as grouped matmuls,
no capacity and no drops, against the plain reference's mixture
(benchmarks/lib/reference_sparse_moe.py; docs/moe.md, guide model-configs
section 4)."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from benchmarks.lib import reference_sparse_moe as ref
from benchmarks.lib.reference_gpt2 import _mm
from horovod_tpu.moe.layer import CHUNK_ROWS, GROUP_ALIGN
from horovod_tpu.monitor.registry import counter

MM = _mm("float32")
N, C, F, E, K = 96, 32, 16, 8, 2


def _params(seed):
    ks = jax.random.split(jax.random.key(seed), 5)
    n = jax.random.normal
    return {"router": n(ks[0], (C, E)), "w1": 0.3 * n(ks[1], (E, C, F)),
            "w3": 0.3 * n(ks[2], (E, C, F)), "w2": 0.3 * n(ks[3], (E, F, C))
            }, n(ks[4], (N, C))


def _share(p, first, held):
    return {"router": p["router"],
            **{n: p[n][first:first + held] for n in ("w1", "w3", "w2")}}


def _whole_reference(p, x):
    s = dict(top_k=K, expert_first=0)
    with jax.default_matmul_precision("highest"):
        return ref.moe(x, p, s, MM)


@pytest.mark.parametrize("held", [8, 4, 2])
def test_the_shares_add_up_to_the_uncut_layer(held):
    """(c): the parts that all the shares of the experts give add up to
    what the uncut reference gives for the whole layer, and each share is
    the reference's share."""
    p, x = _params(0)
    with jax.default_matmul_precision("highest"):
        parts = [hvd.moe_ffn_dropless(x, _share(p, first, held),
                                      experts_per_token=K,
                                      first_expert=first)[0]
                 for first in range(0, E, held)]
        experts, gates = ref.route(x, p["router"], K, MM)
        for i, first in enumerate(range(0, E, held)):
            want = ref.moe_share(x, _share(p, first, held), experts, gates,
                                 first, MM)
            np.testing.assert_allclose(np.asarray(parts[i]),
                                       np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(sum(parts)),
                               np.asarray(_whole_reference(p, x)), atol=5e-5)


def test_no_token_is_dropped_under_skewed_routing():
    """Every token's first choice is expert 3: a capacity layer would drop
    most of them (moe_ffn at capacity_factor 1.25 keeps 30 of 96 there);
    here expert 3 computes all 96 and the result is still the
    reference's."""
    p, x = _params(1)
    logits = jnp.zeros((N, E)).at[:, 3].set(8.0).at[:, 5].set(
        jnp.linspace(0.0, 1.0, N))
    share = _share(p, 2, 4)
    with jax.default_matmul_precision("highest"):
        y, aux = hvd.moe_ffn_dropless(x, share, experts_per_token=K,
                                      first_expert=2, router_logits=logits)
        probs = jax.nn.softmax(logits, -1)
        gates, experts = jax.lax.top_k(probs, K)
        gates = gates / gates.sum(-1, keepdims=True)
        want = ref.moe_share(x, share, experts, gates, 2, MM)
    assert float(aux.load[3]) == N and float(aux.dropped_fraction) == 0.0
    assert float(aux.load.sum()) == N * K
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    assert float(jnp.abs(y).min(axis=-1).max()) > 0   # no token came back 0


@pytest.mark.parametrize("skewed", [False, True])
def test_even_and_skewed_routing_agree_with_the_reference(skewed):
    """1024 tokens, two choices each, one held expert of eight. Routed
    evenly the held expert gets about 256 of the 2048 token-choices; with
    every token's first choice on it, it gets 1024. The same program takes
    both and nothing is dropped either way, value and gradients."""
    n = 1024
    ks = jax.random.split(jax.random.key(8), 3)
    p, _ = _params(6)
    x = jax.random.normal(ks[0], (n, C))
    ct = jax.random.normal(ks[1], (n, C))
    logits = jax.random.normal(ks[2], (n, E))
    if skewed:
        logits = logits.at[:, 5].set(9.0)
    share = _share(p, 5, 1)
    rows = counter("moe.rows_grouped")
    before = rows.value

    def got(x, share):
        y, aux = hvd.moe_ffn_dropless(x, share, experts_per_token=K,
                                      first_expert=5, router_logits=logits)
        return (y * ct).sum(), aux

    def want(x, share):
        gates, experts = jax.lax.top_k(jax.nn.softmax(logits, -1), K)
        gates = gates / gates.sum(-1, keepdims=True)
        return (ref.moe_share(x, share, experts, gates, 5, MM) * ct).sum()

    with jax.default_matmul_precision("highest"):
        (v_got, aux), g_got = jax.value_and_grad(got, argnums=(0, 1),
                                                 has_aux=True)(x, share)
        v_want, g_want = jax.value_and_grad(want, argnums=(0, 1))(x, share)
    assert rows.value - before == n * K + GROUP_ALIGN
    assert (float(aux.load[5]) > 512) == skewed
    np.testing.assert_allclose(float(v_got), float(v_want), rtol=1e-5)
    for name in ("w1", "w3", "w2"):
        a, b = g_got[1][name], g_want[1][name]
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5 * float(jnp.abs(b).max()))
    np.testing.assert_allclose(np.asarray(g_got[0]), np.asarray(g_want[0]),
                               atol=1e-5 * float(jnp.abs(g_want[0]).max()))


ROUTINGS = ["even", "skewed", "none_held", "all_held"]


def _routed(routing, n=1024, held=2, first=4):
    """x ``[n, C]`` whose first feature is 1 and a router kernel whose
    first row pushes the routing: evenly over the eight experts; every
    token's first choice on the last held expert; no choice on a held
    expert; every choice on a held expert (the whole buffer's N * K rows:
    the old worst case)."""
    p, _ = _params(6)
    x = jax.random.normal(jax.random.key(2), (n, C)).at[:, 0].set(1.0)
    push = jnp.zeros((E,))
    if routing == "skewed":
        push = push.at[first + held - 1].set(9.0 * C)
    if routing == "none_held":
        push = push.at[first:first + held].set(-9.0 * C)
    if routing == "all_held":
        push = push.at[first:first + held].set(9.0 * C)
    p["router"] = p["router"].at[0].set(push)
    return x, _share(p, first, held), first, held


def _watch_ragged_dot(monkeypatch):
    """Every ``lax.ragged_dot`` of the layer reports the rows it was handed
    and its group sizes each time it RUNS (a trip of the walk at a time)."""
    seen = []
    real = jax.lax.ragged_dot

    def watched(a, b, sizes, **kw):
        jax.debug.callback(lambda s, rows=a.shape[0]: seen.append(
            (rows, np.asarray(s))), sizes)
        return real(a, b, sizes, **kw)

    monkeypatch.setattr(jax.lax, "ragged_dot", watched)
    return seen


@pytest.mark.parametrize("routing", ROUTINGS)
def test_the_walk_hands_ragged_dot_the_filled_rows(routing, monkeypatch):
    """Whatever the held experts are sent, the rows handed to each of the
    three grouped matmuls over the walk add up to every held expert's load
    rounded up to a tile, ``rows_filled``, and never to more; each trip's
    groups lie on tile boundaries; the trips are ``ceil(rows_filled /
    CHUNK_ROWS)`` of the ``moe.row_chunks`` the buffer has."""
    x, share, first, held = _routed(routing)
    n = x.shape[0]
    seen = _watch_ragged_dot(monkeypatch)
    chunks = counter("moe.row_chunks")
    before = chunks.value
    _, aux = hvd.moe_ffn_dropless(x, share, experts_per_token=K,
                                  first_expert=first)
    jax.effects_barrier()
    load = np.asarray(aux.load[first:first + held]).astype(int)
    assert (load.sum() == 0) == (routing == "none_held")
    assert (load[-1] == n) == (routing in ("skewed", "all_held"))
    assert (load.sum() == n * K) == (routing == "all_held")
    padded = -(-load // GROUP_ALIGN) * GROUP_ALIGN
    filled = int(hvd.moe.rows_filled(aux.load, first, held))
    assert filled == padded.sum() <= hvd.moe.rows_grouped(n * K, held)
    trips = -(-filled // CHUNK_ROWS)
    assert len(seen) == 3 * trips
    assert chunks.value - before == hvd.moe.rows_grouped(
        n * K, held) // CHUNK_ROWS >= trips
    for rows, sizes in seen:
        assert rows == CHUNK_ROWS >= sizes.sum()
        assert not (sizes % GROUP_ALIGN).any()
    for dot in range(3):
        np.testing.assert_array_equal(
            sum(sizes for _, sizes in seen[dot::3]), padded)


@pytest.mark.parametrize("over", [0, 1])
def test_one_row_over_a_chunk_boundary_is_one_more_trip(over, monkeypatch):
    """Work grows with the rows in steps of one chunk and no larger: a held
    expert sent exactly two chunks' rows is walked in two trips, one row
    more in three."""
    n, sent = 4 * CHUNK_ROWS, 2 * CHUNK_ROWS + over
    p, _ = _params(6)
    x = jax.random.normal(jax.random.key(4), (n, C))
    logits = jnp.zeros((n, E)).at[:, 0].set(5.0).at[:, 1].set(4.0)
    logits = logits.at[:sent, 5].set(9.0)
    seen = _watch_ragged_dot(monkeypatch)
    _, aux = hvd.moe_ffn_dropless(x, _share(p, 5, 1), experts_per_token=K,
                                  first_expert=5, router_logits=logits)
    jax.effects_barrier()
    assert int(aux.load[5]) == sent
    assert len(seen) == 3 * (2 + over)


def _jaxprs(eqn):
    for v in eqn.params.values():
        for u in v if isinstance(v, (tuple, list)) else (v,):
            if hasattr(u, "eqns") or hasattr(u, "jaxpr"):
                yield getattr(u, "jaxpr", u)


def test_one_program_takes_any_routing():
    """No branch on the load and no buffer sized by it: the differentiated
    layer is one jaxpr with no ``cond``; its grouped matmuls sit in loops
    (forward and backward) whose trips are a value of the step, each over
    one chunk of rows, and the most they can walk is the buffer of all
    N * K token-choices and a tile a held expert."""
    n = 1024
    p, _ = _params(6)
    x = jax.random.normal(jax.random.key(1), (n, C))

    def loss(x, share):
        y, _ = hvd.moe_ffn_dropless(x, share, experts_per_token=K,
                                    first_expert=5)
        return (y ** 2).sum()

    names, rows = [], []

    def walk(jaxpr, loops):
        for e in jaxpr.eqns:
            names.append(e.primitive.name)
            if e.primitive.name.startswith("ragged_dot"):
                rows.append((e.invars[0].aval.shape[0], loops))
            for j in _jaxprs(e):
                walk(j, loops + (e.primitive.name == "while"))

    chunks = counter("moe.row_chunks")
    before = chunks.value
    walk(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
        x, _share(p, 5, 1)).jaxpr, 0)
    assert "cond" not in names and names.count("while") == 2
    assert len(rows) >= 8 and set(rows) == {(CHUNK_ROWS, 1)}
    assert (chunks.value - before) * CHUNK_ROWS >= n * K + GROUP_ALIGN


class _Remat(nn.Module):
    first: int

    @nn.compact
    def __call__(self, x, share):
        return hvd.moe_ffn_dropless(x, share, experts_per_token=K,
                                    first_expert=self.first)[0]


@pytest.mark.parametrize("wrapped", [False, True],
                         ids=["plain", "remat_jit"])
@pytest.mark.parametrize("routing", ROUTINGS)
def test_value_and_gradients_under_every_routing(routing, wrapped):
    """Value and gradients in x, the three expert weights AND the router
    kernel (through the gates) are the plain loop's under each routing,
    also inside ``nn.remat`` under ``jax.jit``, where the forward walk runs
    again before the backward one."""
    x, share, first, held = _routed(routing)
    ct = jax.random.normal(jax.random.key(7), x.shape)

    def got(x, share):
        if wrapped:
            y = nn.remat(_Remat)(first).apply({}, x, share)
        else:
            y = hvd.moe_ffn_dropless(x, share, experts_per_token=K,
                                     first_expert=first)[0]
        return (y * ct).sum()

    def want(x, share):
        experts, gates = ref.route(x, share["router"], K, MM)
        return (ref.moe_share(x, share, experts, gates, first, MM)
                * ct).sum()

    with jax.default_matmul_precision("highest"):
        f = jax.value_and_grad(got, argnums=(0, 1))
        v_got, g_got = (jax.jit(f) if wrapped else f)(x, share)
        v_want, g_want = jax.value_and_grad(want, argnums=(0, 1))(x, share)
    np.testing.assert_allclose(float(v_got), float(v_want), rtol=2e-5,
                               atol=1e-4)
    got_leaves = {"x": g_got[0], **g_got[1]}
    want_leaves = {"x": g_want[0], **g_want[1]}
    assert set(got_leaves) == {"x", "router", "w1", "w3", "w2"}
    for name, b in want_leaves.items():
        np.testing.assert_allclose(
            np.asarray(got_leaves[name]), np.asarray(b), err_msg=name,
            atol=2e-5 * max(float(jnp.abs(b).max()), 1e-3))
    # (A first choice pushed this hard saturates its gate: "skewed" moves
    # the router by next to nothing, and in the reference too.)
    if routing != "skewed":
        moved = routing != "none_held"
        assert (float(jnp.abs(got_leaves["router"]).max()) > 0) == moved


def test_the_backward_walk_carries_the_scope():
    """The scope is opened outside the ``custom_vjp``, so the backward
    walk's ops carry it (``moe_ffn.ms`` reads both directions by it), and
    its grouped matmuls are still ``ragged_dot``s by name (the structure
    row ``grouped_matmuls_in_program`` counts that text)."""
    import re

    from horovod_tpu.monitor.span_audit import DEVICE_SCOPES

    assert "hvd.moe_ffn" in DEVICE_SCOPES
    x, share, first, _ = _routed("even")
    traced = jax.jit(jax.grad(lambda x, share: (hvd.moe_ffn_dropless(
        x, share, experts_per_token=K, first_expert=first)[0] ** 2).sum(),
        argnums=(0, 1))).trace(x, share)
    # As the TPU is handed it: grouped matmuls by name, three forward and
    # five backward (the hidden rows again, dh, dx's two).
    assert traced.lower(lowering_platforms=("tpu",)).as_text().count(
        '"chlo.ragged_dot"') >= 8
    lowered = traced.lower()
    where = "\n".join(line for line in lowered.as_text(
        debug_info=True).splitlines() if "ragged_dot" in line)
    assert "/jvp(hvd.moe_ffn)/while/body/ragged_dot" in where
    assert "/transpose(jvp(hvd.moe_ffn))/while/body/ragged_dot" in where
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    backward = [n for n in names if "transpose(" in n and "/while/" in n]
    assert backward and all("hvd.moe_ffn" in n for n in backward)


def test_gradients_are_the_references():
    p, x = _params(2)
    share = _share(p, 4, 4)
    ct = jax.random.normal(jax.random.key(7), (N, C))

    def got(x, share):
        return (hvd.moe_ffn_dropless(x, share, experts_per_token=K,
                                     first_expert=4)[0] * ct).sum()

    def want(x, share):
        experts, gates = ref.route(x, share["router"], K, MM)
        return (ref.moe_share(x, share, experts, gates, 4, MM) * ct).sum()

    with jax.default_matmul_precision("highest"):
        g_got = jax.grad(got, argnums=(0, 1))(x, share)
        g_want = jax.grad(want, argnums=(0, 1))(x, share)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5 * max(1.0, float(
                                       jnp.abs(b).max())))


def test_absent_experts_add_nothing_and_holding_none_of_a_tokens_is_zero():
    p, x = _params(3)
    logits = jnp.zeros((N, E)).at[:, 0].set(5.0).at[:, 1].set(4.0)
    y, _ = hvd.moe_ffn_dropless(x, _share(p, 4, 4), experts_per_token=K,
                                first_expert=4, router_logits=logits)
    assert not np.asarray(y).any()


def test_counters_and_refusals():
    p, x = _params(4)
    held, rows = counter("moe.experts_held"), counter("moe.rows_grouped")
    before = held.value, rows.value
    hvd.moe_ffn_dropless(x, _share(p, 2, 4), experts_per_token=K,
                         first_expert=2)
    assert (held.value - before[0], rows.value - before[1]) == (
        4, GROUP_ALIGN + 4 * GROUP_ALIGN)      # N * K = 192 rows fill a tile
    with pytest.raises(ValueError, match="not among the router's"):
        hvd.moe_ffn_dropless(x, _share(p, 0, 4), experts_per_token=K,
                             first_expert=6)


def test_the_exchange_across_ep_is_not_built():
    p, x = _params(5)
    hvd.shutdown()
    hvd.init(ep_size=2)
    try:
        from jax.sharding import PartitionSpec as P

        def f(x):
            return hvd.moe_ffn_dropless(
                x, _share(p, 0, 4), experts_per_token=K,
                ep_axis=hvd.EP_AXIS if hasattr(hvd, "EP_AXIS")
                else "hvd_ep")[0]

        with pytest.raises(NotImplementedError, match="ROADMAP R1"):
            jax.jit(hvd.shard_map(f, mesh=hvd.mesh(), in_specs=P(),
                                  out_specs=P()))(x)
    finally:
        hvd.shutdown()
        hvd.init()


# -- the sigmoid router, its selection bias and the shared expert -------------
# (benchmarks/lib/reference_afmoe.py; docs/moe.md "Scoring kinds")

from benchmarks.lib import reference_afmoe as afref  # noqa: E402
from horovod_tpu.moe.layer import moe_router, router_bias_update  # noqa: E402

SCALE = 2.826
AF = dict(top_k=K, route_norm=True, route_scale=SCALE, expert_first=0,
          experts=E, shared=1)


def _plain_sigmoid_route(x, router, bias, norm=True, scale=SCALE):
    """A loop a token: scores, the K largest of score + bias, gates from
    the scores alone."""
    scores = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                                   @ np.asarray(router, np.float64))))
    experts, gates = [], []
    for row in scores:
        chosen = np.argsort(-(row + np.asarray(bias, np.float64)),
                            kind="stable")[:K]
        g = row[chosen]
        if norm:
            g = g / (g.sum() + 1e-20)
        experts.append(chosen)
        gates.append(g * scale)
    return np.asarray(experts), np.asarray(gates)


@pytest.mark.parametrize("norm, scale", [(True, SCALE), (True, 1.0),
                                         (False, 1.0), (False, 0.5)])
@pytest.mark.parametrize("biased", [False, True])
def test_sigmoid_router_is_the_plain_loop(biased, norm, scale):
    p, x = _params(2)
    bias = (0.3 * jax.random.normal(jax.random.key(9), (E,)) if biased
            else jnp.zeros((E,)))
    with jax.default_matmul_precision("highest"):
        experts, gates, _, _, scores = moe_router(
            x, p["router"], topk=K, scoring="sigmoid", bias=bias,
            route_norm=norm, route_scale=scale)
    want_e, want_g = _plain_sigmoid_route(x, p["router"], bias, norm, scale)
    np.testing.assert_array_equal(np.asarray(experts), want_e)
    np.testing.assert_allclose(np.asarray(gates), want_g, rtol=2e-5)
    assert float(scores.min()) >= 0 and float(scores.max()) <= 1


def test_the_bias_chooses_and_never_weighs():
    """A large bias on expert 6 puts it among every token's choices; the
    gates are still made of the scores alone, so the same choices forced
    without a bias give the same gates, and no gradient reaches the
    bias."""
    p, x = _params(3)
    bias = jnp.zeros((E,)).at[6].set(5.0)
    experts, gates, *_ = moe_router(x, p["router"], topk=K,
                                    scoring="sigmoid", bias=bias,
                                    route_scale=SCALE)
    assert bool((experts == 6).any(axis=-1).all())
    scores = jax.nn.sigmoid(x @ p["router"])
    picked = jnp.take_along_axis(scores, experts, -1)
    np.testing.assert_allclose(
        np.asarray(gates),
        np.asarray(SCALE * picked / picked.sum(-1, keepdims=True)),
        rtol=1e-5)

    def loss(bias, router):
        _, g, *_ = moe_router(x, router, topk=K, scoring="sigmoid",
                              bias=bias, route_scale=SCALE)
        return (g ** 2).sum()

    d_bias, d_router = jax.grad(loss, argnums=(0, 1))(bias, p["router"])
    assert float(jnp.abs(d_bias).max()) == 0.0
    assert float(jnp.abs(d_router).max()) > 0.0


def test_softmax_scoring_refuses_the_sigmoid_options():
    p, x = _params(3)
    for bad in (dict(bias=jnp.zeros((E,))), dict(route_norm=False),
                dict(route_scale=2.0), dict(scoring="tanh")):
        with pytest.raises(ValueError):
            moe_router(x, p["router"], topk=K, **bad)


@pytest.mark.parametrize("coeff", [0.001, 0.01])
def test_bias_update_rule(coeff):
    """delta = coeff * sign(mean(n) - n), re-centred; the biases' sum stays
    put, an overloaded expert's goes down, an expert at the mean moves by
    the re-centring alone."""
    load = jnp.asarray([40.0, 10.0, 24.0, 24.0, 30.0, 20.0, 24.0, 20.0])
    bias = jnp.linspace(-0.01, 0.01, E)
    got = router_bias_update(bias, load, coeff=coeff)
    sign = np.sign(24.0 - np.asarray(load))
    delta = coeff * sign
    want = np.asarray(bias) + delta - delta.mean()
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-8)
    np.testing.assert_allclose(float(got.sum()), float(bias.sum()),
                               atol=1e-7)
    assert got[0] < bias[0] and got[1] > bias[1]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(afref.bias_update(bias, load,
                                                            coeff)),
                               atol=1e-8)
    assert float(jnp.abs(jax.grad(lambda b: router_bias_update(
        b, load, coeff=coeff).sum())(bias) - 1.0).max()) == 0.0


def _afmoe_params(seed):
    p, x = _params(seed)
    ks = jax.random.split(jax.random.key(100 + seed), 3)
    p["shared"] = {"w1": 0.3 * jax.random.normal(ks[0], (C, F)),
                   "w3": 0.3 * jax.random.normal(ks[1], (C, F)),
                   "w2": 0.3 * jax.random.normal(ks[2], (F, C))}
    return p, x


@pytest.mark.parametrize("held", [8, 4, 1])
@pytest.mark.parametrize("biased", [False, True])
def test_sigmoid_shares_add_up_with_the_shared_expert_counted_once(held,
                                                                   biased):
    """E / held shares of ``held`` experts each (eight shares of 16 in the
    cell), every one routed by the sigmoid router with the same bias, plus
    the shared expert ONCE, equal the uncut reference layer; each share is
    the reference's share; the counts are over all the experts."""
    p, x = _afmoe_params(4)
    bias = (0.2 * jax.random.normal(jax.random.key(5), (E,)) if biased
            else jnp.zeros((E,)))
    router = dict(scoring="sigmoid", bias=bias, route_norm=True,
                  route_scale=SCALE)
    with jax.default_matmul_precision("highest"):
        parts, loads = zip(*[
            hvd.moe_ffn_dropless(x, _share(p, first, held),
                                 experts_per_token=K, first_expert=first,
                                 **router) for first in range(0, E, held)])
        experts, gates = afref.route(x, p["router"], bias, AF, MM)
        for part, first in zip(parts, range(0, E, held)):
            want = afref.moe_share(x, _share(p, first, held), experts,
                                   gates, first, MM)
            np.testing.assert_allclose(np.asarray(part), np.asarray(want),
                                       atol=5e-5)
        shared = afref.gated_mlp(x, p["shared"], MM)
        whole, counts = afref.moe(x, p, bias, AF, MM)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), atol=1e-4)
    for aux in loads:
        np.testing.assert_array_equal(np.asarray(aux.load),
                                      np.asarray(counts))
    assert float(counts.sum()) == N * K


def test_scoring_is_counted():
    p, x = _params(0)
    before = {k: counter("moe.scoring", kind=k).value
              for k in ("softmax", "sigmoid")}
    hvd.moe_ffn_dropless(x, p, experts_per_token=K)
    hvd.moe_ffn_dropless(x, p, experts_per_token=K, scoring="sigmoid",
                         route_scale=SCALE)
    assert {k: counter("moe.scoring", kind=k).value - before[k]
            for k in before} == {"softmax": 1, "sigmoid": 1}


# -- the walk's plan under a name (``PLAN_NAME``) -----------------------------
# What lays the walk out carries a ``checkpoint_name``: a rematerialised
# caller that keeps it runs no top-k and no sort a second time
# (models/sparse_moe_decoder.py ``remat_kept``).

from horovod_tpu.moe.layer import PLAN_NAME, plan_bytes  # noqa: E402

ROUTERS = {"softmax": {}, "sigmoid": dict(scoring="sigmoid",
                                          route_scale=SCALE)}


def _equations(jaxpr, recomputed=False):
    """(primitive name, whether inside a ``jax.checkpoint``'s backward
    equation, equation) of ``jaxpr`` and every jaxpr nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, recomputed, eqn
        for sub in _jaxprs(eqn):
            yield from _equations(
                sub, recomputed or eqn.primitive.name == "remat2")


@pytest.mark.parametrize("scoring", sorted(ROUTERS))
def test_the_plan_carries_its_name(scoring):
    """Chosen experts, their scores, ``order`` and ``sizes``, and nothing
    else, carry ``PLAN_NAME``; ``plan_bytes`` is their size."""
    p, x = _params(6)
    share = _share(p, 2, 4)
    named = [eqn.outvars[0].aval for name, _, eqn in _equations(
        jax.make_jaxpr(lambda x: hvd.moe_ffn_dropless(
            x, share, experts_per_token=K, first_expert=2,
            **ROUTERS[scoring])[0])(x).jaxpr)
        if name == "name" and eqn.params["name"] == PLAN_NAME]
    assert sorted((a.shape, str(a.dtype)) for a in named) == sorted([
        ((N, K), "int32"), ((N, K), "float32"), ((N * K,), "int32"),
        ((4,), "int32")])
    assert sum(a.size * a.dtype.itemsize for a in named) == plan_bytes(
        N * K, 4)


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "not_kept"])
@pytest.mark.parametrize("scoring", sorted(ROUTERS))
def test_a_kept_plan_is_not_made_again(scoring, kept):
    """Under ``jax.checkpoint`` a policy that saves ``PLAN_NAME`` leaves no
    top-k and no sort in the backward pass, one that saves nothing has
    both again; value and gradients (x, the weights, the router through
    the gates) are the same to the bit either way."""
    p, x = _params(7)
    share = _share(p, 2, 4)
    ct = jax.random.normal(jax.random.key(8), x.shape)
    policies = jax.checkpoint_policies

    def loss(policy):
        layer = jax.checkpoint(lambda x, share: hvd.moe_ffn_dropless(
            x, share, experts_per_token=K, first_expert=2,
            **ROUTERS[scoring])[0], policy=policy)
        return lambda x, share: (jnp.tanh(layer(x, share)) * ct).sum()

    policy = (policies.save_only_these_names(PLAN_NAME) if kept
              else policies.nothing_saveable)
    f = jax.value_and_grad(loss(policy), argnums=(0, 1))
    again = [name for name, recomputed, _ in _equations(
        jax.make_jaxpr(f)(x, share).jaxpr)
        if recomputed and name in ("top_k", "sort")]
    assert sorted(again) == ([] if kept else ["sort", "top_k"])
    got = jax.jit(f)(x, share)
    want = jax.jit(jax.value_and_grad(loss(policies.nothing_saveable),
                                      argnums=(0, 1)))(x, share)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(got[1][1]["router"]).max()) > 0
