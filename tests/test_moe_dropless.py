"""``hvd.moe_ffn_dropless``: the experts a chip holds as grouped matmuls,
no capacity and no drops, against the plain reference's mixture
(benchmarks/lib/reference_sparse_moe.py; docs/moe.md, guide model-configs
section 4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from benchmarks.lib import reference_sparse_moe as ref
from benchmarks.lib.reference_gpt2 import _mm
from horovod_tpu.moe.layer import GROUP_ALIGN
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


@pytest.mark.parametrize("routing", ["even", "skewed", "none_held"])
def test_every_routing_runs_every_tile(routing, monkeypatch):
    """The layer's time must not follow the routing: whatever the held
    experts are sent, the grouped matmuls are handed groups that start on
    tile boundaries, hold their expert's load and add up to the whole
    buffer, so the same row tiles run, each for one expert."""
    n, held, first = 1024, 2, 4
    p, _ = _params(6)
    x = jax.random.normal(jax.random.key(2), (n, C))
    logits = jax.random.normal(jax.random.key(3), (n, E))
    if routing == "skewed":
        logits = logits.at[:, 5].set(9.0)
    if routing == "none_held":
        logits = logits.at[:, 4:6].set(-9.0)
    seen = []
    real = jax.lax.ragged_dot
    monkeypatch.setattr(jax.lax, "ragged_dot", lambda a, b, sizes: (
        seen.append((a.shape[0], np.asarray(sizes))), real(a, b, sizes))[1])
    _, aux = hvd.moe_ffn_dropless(x, _share(p, first, held),
                                  experts_per_token=K, first_expert=first,
                                  router_logits=logits)
    load = np.asarray(aux.load[first:first + held])
    assert (load.sum() == 0) == (routing == "none_held")
    assert (load[1] == n) == (routing == "skewed")
    assert len(seen) == 3
    for rows, sizes in seen:
        assert rows == n * K + held * GROUP_ALIGN == sizes.sum()
        assert not (sizes % GROUP_ALIGN).any() and (sizes >= load).all()


def _jaxprs(eqn):
    for v in eqn.params.values():
        for u in v if isinstance(v, (tuple, list)) else (v,):
            if hasattr(u, "eqns") or hasattr(u, "jaxpr"):
                yield getattr(u, "jaxpr", u)


def test_one_program_takes_any_routing():
    """No branch on the load: the differentiated layer holds no ``cond``,
    and its grouped matmuls run over a buffer of all N * K token-choices
    and a tile a held expert, the most the held experts can be sent
    (buffers sized by the observed load are ROADMAP S13)."""
    n = 1024
    p, _ = _params(6)
    x = jax.random.normal(jax.random.key(1), (n, C))

    def loss(x, share):
        y, _ = hvd.moe_ffn_dropless(x, share, experts_per_token=K,
                                    first_expert=5)
        return (y ** 2).sum()

    names, rows = [], set()

    def walk(jaxpr):
        for e in jaxpr.eqns:
            names.append(e.primitive.name)
            if e.primitive.name.startswith("ragged_dot"):
                rows.add(e.invars[0].aval.shape[0])
            for j in _jaxprs(e):
                walk(j)

    walk(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
        x, _share(p, 5, 1)).jaxpr)
    assert "cond" not in names and "while" not in names
    assert rows == {n * K + GROUP_ALIGN}      # one held expert


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
