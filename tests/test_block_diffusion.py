"""The block-diffusion objective (docs/block_diffusion.md): the flash kernels
under its mask against a dense masked softmax built from the mask's
definition, their tile counters, the noise, the loss, and a tiny ``sdar_moe``
model against the plain reference (benchmarks/lib/reference_sdar.py)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from benchmarks.lib import reference_sdar as ref
from benchmarks.lib.reference_gpt2 import _mm
from horovod_tpu.models import SparseMoEConfig, SparseMoEDecoder
from horovod_tpu.models import sparse_moe_decoder as decoder
from horovod_tpu.monitor.registry import counter
from horovod_tpu.ops import flash_attention as fa

KERNELS = ("fwd", "bwd_dq", "bwd_dkv")


def _seen(L, B):
    """The mask from its definition, [2L, 2L] numpy bool: rows and columns
    ``[noised ; clean]``."""
    r = np.arange(2 * L)
    clean, blk = r >= L, (r % L) // B
    qc, kc = clean[:, None], clean[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return ((qc & kc & (kb <= qb)) | (~qc & kc & (kb < qb))
            | (~qc & ~kc & (kb == qb)))


def _dense(q, k, v, B):
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(_seen(q.shape[1] // 2, B), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _operands(Bt, L, H, Hk, D, seed=0):
    rs = np.random.RandomState(seed)
    q, k, v, w = (jnp.asarray(rs.randn(Bt, 2 * L, n, D), jnp.float32)
                  for n in (H, Hk, Hk, H))
    return 0.5 * q, 0.5 * k, v, w


def test_the_mask_holds_l2_plus_lb_pairs_and_every_row_sees_itself():
    for L, B in ((8, 2), (64, 4), (256, 8)):
        seen = _seen(L, B)
        assert seen.sum() == L * L + L * B
        assert seen.diagonal().all()
        assert not seen[:L, :L][np.arange(L)[:, None] // B
                                != np.arange(L)[None, :] // B].any()
        np.testing.assert_array_equal(
            np.asarray(fa.block_diffusion_mask(L, B)), seen)


@pytest.mark.parametrize("L, B, H, Hk, D, block", [
    (256, 4, 8, 2, 128, 128),     # two blocks a half, in place, grouped
    (256, 8, 8, 2, 128, 1024),    # one block a half
    (1024, 4, 8, 2, 128, 256),    # four blocks a half, 256 x 256 sub-tiles
    (1024, 8, 8, 2, 128, 1024),   # one block of four sub-tiles a side
    (256, 4, 4, 4, 64, 128),      # a pair of heads a lane block
    (256, 8, 3, 1, 32, 128),      # packed
])
def test_kernels_match_the_dense_masked_softmax(L, B, H, Hk, D, block):
    """Forward, dq and dk/dv of ``flash_attention(block_diffusion=B)``
    (Pallas interpreter) against a dense softmax under the mask as its
    definition gives it: values to 2e-6, gradients to 5e-6 of the largest
    entry (float32 sums in another order)."""
    q, k, v, w = _operands(2 if D == 32 else 1, L, H, Hk, D)
    call = functools.partial(fa.flash_attention, block_diffusion=B,
                             block_q=block, block_k=block)
    text = str(jax.make_jaxpr(jax.grad(
        lambda q: call(q, k, v).sum()))(q))
    assert all(f"hvd_flash_{n}_bd" in text for n in KERNELS)
    np.testing.assert_allclose(np.asarray(call(q, k, v)),
                               np.asarray(_dense(q, k, v, B)), atol=2e-6)
    got = jax.grad(lambda *a: (call(*a) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_dense(*a, B) * w).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-6 * float(jnp.abs(b).max()))


def test_bfloat16_operands_and_a_batch():
    q, k, v, _ = _operands(2, 256, 8, 2, 128, seed=1)
    got = fa.flash_attention(*(x.astype(jnp.bfloat16) for x in (q, k, v)),
                             block_diffusion=4, block_q=128, block_k=128)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(_dense(q, k, v, 4)), atol=3e-2)


def test_a_sequence_no_block_divides_takes_the_dense_path():
    """L = 36 is no multiple of a sub-tile of whole blocks of 8 ... the
    dense fallback holds the same mask."""
    q, k, v, _ = _operands(1, 40, 4, 2, 16)
    got = fa.flash_attention(q, k, v, block_diffusion=8, block_q=128,
                             block_k=128)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dense(q, k, v, 8)), atol=2e-6)
    got = fa._dense_fallback(q, k, v, True, None, None, 8)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dense(q, k, v, 8)), atol=2e-6)


@pytest.mark.parametrize("bad", [dict(block_diffusion=3),
                                 dict(block_diffusion=4, window=8),
                                 dict(block_diffusion=4, causal=False),
                                 dict(block_diffusion=16)])
def test_rejects_what_is_no_block_diffusion_call(bad):
    q, k, v, _ = _operands(1, 12, 2, 2, 16)    # 24 rows: no 2 x 16
    with pytest.raises(ValueError, match="block"):
        fa.flash_attention(q, k, v, **bad)


def _brute_tiles(L, B, b, t):
    """(total, computed, masked) by the counters' rule from the mask
    itself: a cell of ``b`` wholly visible is one tile; a cell with an
    edge in it is cut into ``t x t`` sub-tiles of which those with a
    visible pair are computed and those not wholly visible masked; a cell
    with nothing visible counts its sub-tiles to the total alone."""
    seen = _seen(L, B)
    total = computed = masked = 0
    for i in range(0, 2 * L, b):
        for j in range(0, 2 * L, b):
            cell = seen[i:i + b, j:j + b]
            if cell.all():
                total, computed = total + 1, computed + 1
                continue
            subs = [cell[a:a + t, c:c + t] for a in range(0, b, t)
                    for c in range(0, b, t)]
            total += len(subs)
            computed += sum(s.any() for s in subs)
            masked += sum(s.any() and not s.all() for s in subs)
    return total, computed, masked


@pytest.mark.parametrize("L, B, block", [(1024, 4, 1024), (1024, 8, 512),
                                         (2048, 4, 512), (512, 4, 128)])
def test_tile_counters_are_the_tiles_the_mask_touches(L, B, block):
    """``flash.tiles_*{block=B}`` of a call: no tile is computed that the
    mask does not touch, and none it touches is left out."""
    t = fa._sub_tile(block, fa._SUB_TILE[0])
    want = _brute_tiles(L, B, block, t)
    assert fa._tile_counts(True, True, 2 * L // block, 2 * L // block, block,
                           block, block=B) == want
    # ... which is no more than the sub-tiles the mask touches (a cell
    # wholly visible is ONE tile, of (block / t)^2 sub-tiles' area)
    seen = _seen(L, B)
    assert want[1] <= sum(seen[a:a + t, c:c + t].any()
                          for a in range(0, 2 * L, t)
                          for c in range(0, 2 * L, t))

    def read():
        return {(n, kern): counter(f"flash.tiles_{n}", kernel=kern,
                                   block=str(B)).value
                for n in ("total", "computed", "masked") for kern in KERNELS}

    before = read()
    q = jax.ShapeDtypeStruct((1, 2 * L, 2, 128), jnp.float32)
    jax.eval_shape(jax.grad(lambda q, k, v: fa.flash_attention(
        q, k, v, block_diffusion=B, block_q=block, block_k=block).sum(),
        (0, 1, 2)), q, q, q)
    after = read()
    for kern in KERNELS:
        assert tuple(after[n, kern] - before[n, kern]
                     for n in ("total", "computed", "masked")) == want


def test_the_grid_visits_the_masks_cells_alone():
    """Blocks of 128 at L = 512: a half has 4 blocks, the forward and dq
    kernels take 5 steps a q block (its own noised block and 4 clean ones
    at the most) and the dk/dv kernel 8 a k block, of the square's 8; every
    step's block index is one the mask touches."""
    n = 4
    fine = _seen(8 * n, 2)      # cells of 8 positions, blocks of 2
    seen = fine.reshape(2 * n, 8, 2 * n, 8).any(axis=(1, 3))
    for i in range(2 * n):
        cells = {fa._bd_k_block(i, j, n) for j in range(n + 1)}
        ran = {fa._bd_k_block(i, j, n) for j in range(n + 1)
               if any(here for _, here in fa._bd_cells(i, j, n))}
        assert cells == ran == set(np.flatnonzero(seen[i]))
    for j in range(2 * n):
        ran = {fa._bd_q_block(j, s, n) for s in range(2 * n)
               if any(here for _, here in fa._bd_cells(j, s, n, True))}
        assert ran == set(np.flatnonzero(seen[:, j]))
    text = str(jax.make_jaxpr(jax.grad(lambda q: fa.flash_attention(
        q, q, q, block_diffusion=4, block_q=128, block_k=128).sum()))(
        jnp.zeros((1, 1024, 1, 128))))
    assert "grid=(1, 1, 8, 1, 5)" in text and "grid=(1, 1, 8, 1, 8)" in text


def test_without_the_mask_the_file_lowers_what_it_lowered():
    """``block_diffusion=None`` is the call without the argument, equation
    for equation: the plain kernels' names, no ``_bd`` kernel, no block
    index in a kernel body, and counters under no ``block`` label."""
    q = jnp.zeros((1, 512, 4, 64))

    def text(**kw):
        return str(jax.make_jaxpr(jax.grad(lambda q: fa.flash_attention(
            q, q, q, block_q=256, block_k=256, **kw).sum()))(q))

    plain = text()
    assert plain == text(block_diffusion=None)
    assert "_bd" not in plain and "shift_right" not in plain
    for name in ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"):
        assert f"name={name}\n" in plain or f"name={name} " in plain
    assert "shift_right" in text(block_diffusion=4)


# -- the objective --------------------------------------------------------------

def test_noise_is_reproducible_from_seed_and_step():
    toks = jax.random.randint(jax.random.key(0), (3, 256), 0, 95)
    key = lambda seed, step: jax.random.fold_in(jax.random.key(seed), step)
    a = hvd.block_diffusion_noise(toks, key(7, 2), block_length=4, mask_id=95)
    b = hvd.block_diffusion_noise(toks, key(7, 2), block_length=4, mask_id=95)
    c = hvd.block_diffusion_noise(toks, key(7, 3), block_length=4, mask_id=95)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert (np.asarray(a.masked) != np.asarray(c.masked)).any()
    # the rows: the noised copy, then the clean one; masked ones hold M
    rows, masked = np.asarray(a.rows), np.asarray(a.masked)
    np.testing.assert_array_equal(rows[:, 256:], np.asarray(toks))
    np.testing.assert_array_equal(rows[:, :256],
                                  np.where(masked, 95, np.asarray(toks)))
    # one level a block, within [eps, 1)
    t = np.asarray(a.t).reshape(3, 64, 4)
    assert (t == t[..., :1]).all() and t.min() >= 1e-3 and t.max() < 1
    # a rank's rows are drawn alike wherever they lie
    later = hvd.block_diffusion_noise(toks[1:], key(7, 2), block_length=4,
                                      mask_id=95, first_row=1)
    np.testing.assert_array_equal(np.asarray(later.masked), masked[1:])
    # and the reference draws the same from (seed, step)
    xt, m, lv = ref.noise(jnp.uint32(7), 2, toks,
                          dict(block_length=4, mask_id=95))
    np.testing.assert_array_equal(np.asarray(xt), rows[:, :256])
    np.testing.assert_array_equal(np.asarray(m), masked)
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(a.t))


def test_the_masked_share_is_near_the_mean_level():
    toks = jnp.zeros((8, 4096), jnp.int32)
    out = hvd.block_diffusion_noise(toks, jax.random.key(1), block_length=4,
                                    mask_id=9)
    share, level = float(out.masked.mean()), float(out.t.mean())
    assert abs(level - 0.5005) < 0.02        # E[t] = eps + (1 - eps) / 2
    assert abs(share - level) < 0.01


def test_the_noise_counts_its_rows_once_a_trace():
    before = {h: counter("block_diffusion.rows", half=h).value
              for h in ("noised", "clean")}
    jax.eval_shape(lambda t: hvd.block_diffusion_noise(
        t, jax.random.key(0), block_length=4, mask_id=9),
        jax.ShapeDtypeStruct((2, 64), jnp.int32))
    for h in ("noised", "clean"):
        assert counter("block_diffusion.rows", half=h).value \
            - before[h] == 128


def test_the_loss_is_the_hand_written_one():
    ks = jax.random.split(jax.random.key(2), 5)
    h = jax.random.normal(ks[0], (2, 16, 8))
    head = jax.random.normal(ks[1], (11, 8))
    toks = jax.random.randint(ks[2], (2, 16), 0, 11)
    masked = jax.random.bernoulli(ks[3], 0.5, (2, 16))
    t = jnp.repeat(jax.random.uniform(ks[4], (2, 4), minval=0.1), 4, axis=1)
    with jax.default_matmul_precision("highest"):
        got = hvd.block_diffusion_loss(h, head, toks, masked, t)
    want = 0.0
    for b in range(2):
        for i in range(16):
            if masked[b, i]:
                logits = np.asarray(head) @ np.asarray(h[b, i])
                ce = np.log(np.exp(logits).sum()) - logits[int(toks[b, i])]
                want += ce / float(t[b, i])
    np.testing.assert_allclose(float(got), want / 16 / 2, rtol=1e-5)


# -- the model against the plain reference --------------------------------------

CFG = {"model_type": "sdar_moe", "layers": 2, "num_hidden_layers": 2,
       "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 16, "vocab_size": 96, "num_experts": 8,
       "num_local_experts": 4, "first_local_expert": 2,
       "num_experts_per_tok": 2, "moe_intermediate_size": 32,
       "intermediate_size": 96, "sliding_window": None,
       "rms_norm_eps": 1e-6, "rope_theta": 1000000, "block_length": 4}
L = 64
SIZES = ref.sizes_from_config(CFG)


def _program_loss(model, noised, x0):
    def loss(p):
        h = model.apply({"params": p}, noised.rows)
        return hvd.block_diffusion_loss(h, p["head"], x0, noised.masked,
                                        noised.t)
    return loss


@pytest.fixture(scope="module")
def float32_pair():
    """(program (loss, grads), reference (loss, grads)) on seeded weights
    and two sequences, the program in float32 at ``highest``: the same
    arithmetic."""
    params = jax.jit(functools.partial(ref.make_params, s=SIZES))(
        jnp.uint32(3))
    x0 = jax.random.randint(jax.random.key(1), (2, L), 0, SIZES["mask_id"])
    key = jax.random.fold_in(jax.random.key(jnp.uint32(5)), 1)
    noised = hvd.block_diffusion_noise(x0, key, block_length=4,
                                       mask_id=SIZES["mask_id"])
    model = SparseMoEDecoder(SparseMoEConfig.from_dict(
        CFG, dtype=jnp.float32, return_hidden=True))
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(_program_loss(model, noised, x0))(params)
    xt, masked, t = ref.noise(jnp.uint32(5), 1, x0, SIZES)
    want = jax.value_and_grad(lambda p: ref.loss_sum(
        p, xt, x0, masked, t, SIZES, q_block=32) / 2)(params)
    return got, want


def test_parameter_tree_and_config_are_the_references():
    cfg = SparseMoEConfig.from_dict(CFG)
    assert cfg.layer_types == (decoder.BLOCK_DIFFUSION,) * 2
    assert (cfg.block_length, cfg.rope_theta, cfg.num_local_experts,
            cfg.first_local_expert) == (4, 1e6, 4, 2)
    want = jax.eval_shape(SparseMoEDecoder(cfg).init, jax.random.key(0),
                          jax.ShapeDtypeStruct((1, 2 * L), jnp.int32))["params"]
    got = jax.eval_shape(functools.partial(ref.make_params, s=SIZES),
                         jax.ShapeDtypeStruct((), jnp.uint32))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    with pytest.raises(ValueError, match="noised and a clean"):
        SparseMoEDecoder(cfg).init(jax.random.key(0),
                                   jnp.zeros((1, 2 * L + 4), jnp.int32))


def test_the_model_hands_out_the_noised_half():
    cfg = SparseMoEConfig.from_dict(CFG)
    out = jax.eval_shape(
        lambda t: SparseMoEDecoder(cfg).init_with_output(
            jax.random.key(0), t)[0], jax.ShapeDtypeStruct((3, 2 * L),
                                                           jnp.int32))
    assert out.shape == (3, L, CFG["vocab_size"])


def test_loss_is_the_references(float32_pair):
    (loss, _), (want, _) = float32_pair
    np.testing.assert_allclose(float(loss), float(want), rtol=2e-6)


@pytest.mark.parametrize("leaf", sorted(ref.path_dict(jax.eval_shape(
    functools.partial(ref.make_params, s=SIZES),
    jax.ShapeDtypeStruct((), jnp.uint32)))))
def test_gradient_leaf_is_the_references(float32_pair, leaf):
    """Every gradient leaf to 2e-5 of the leaf's largest entry (float32
    rounding through two layers and 1 / t up to 1000)."""
    (_, got), (_, want) = float32_pair
    a, b = ref.path_dict(got)[leaf], ref.path_dict(want)[leaf]
    assert float(jnp.abs(b).max()) > 0
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=2e-5 * float(jnp.abs(b).max()))


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Guide model-configs, section 4, on a layer's 2 L rows: the parts
    that the eight shares of the experts give (one expert each), with the
    attention every chip computes alike counted once, add up to what the
    uncut reference gives for the whole layer."""
    E = CFG["num_experts"]
    whole = dict(SIZES, experts_held=E, expert_first=0, layers=1)
    p = jax.jit(functools.partial(ref.make_params, s=whole))(
        jnp.uint32(4))["h0"]
    x = jax.random.normal(jax.random.key(6), (2 * L, CFG["hidden_size"]))
    positions = jnp.arange(2 * L) % L
    mm = _mm("float32")
    with jax.default_matmul_precision("highest"):
        want = ref._block(x, p, whole, mm, 32)

        def share(first, held):
            cfg = SparseMoEConfig.from_dict(
                dict(CFG, layers=1, num_local_experts=held,
                     first_local_expert=first), dtype=jnp.float32)
            mine = dict(p, moe={
                "router": p["moe"]["router"],
                **{n: p["moe"][n][first:first + held]
                   for n in ("w1", "w3", "w2")}})
            return decoder._Block(cfg, 0).apply(
                {"params": mine}, x[None], positions)[0][0]

        parts = [share(e, 1) for e in range(E)]
        none = dict(p, moe=dict(p["moe"], w2=jnp.zeros_like(p["moe"]["w2"])))
        h = decoder._Block(SparseMoEConfig.from_dict(
            dict(CFG, layers=1, num_local_experts=E, first_local_expert=0),
            dtype=jnp.float32), 0).apply({"params": none}, x[None],
                                         positions)[0][0]
    got = h + sum(part - h for part in parts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert float(jnp.abs(want - h).max()) > 1e-3   # the experts add something
