"""Kernel block-size autotuner (ops/kernel_autotune.py) — cache and
dispatch logic. The sweep's timing machinery only means anything on a
real TPU (see the module docstring), so these tests drive get_or_tune
with canned bench functions; the real-hardware proof is the flagship
bench converging to >= the hand-tuned number with a fresh cache."""

import json

import pytest

import horovod_tpu.ops.kernel_autotune as at


@pytest.fixture()
def fresh_cache(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("HOROVOD_AUTOTUNE_CACHE", str(path))
    monkeypatch.setattr(at, "_mem", {})
    monkeypatch.setattr(at, "_loaded", False)
    yield path


class TestGetOrTune:
    def test_disabled_off_tpu_returns_default(self, fresh_cache):
        # CPU test env: enabled() is False -> default, no bench calls.
        calls = []
        out = at.get_or_tune("k", "s", [(1,), (2,)],
                             lambda c: calls.append(c) or 0.1, (9,))
        assert out == (9,) and calls == []

    def test_sweep_picks_fastest_and_caches(self, fresh_cache, monkeypatch):
        monkeypatch.setattr(at, "enabled", lambda: True)
        times = {(256,): 0.003, (512,): 0.001, (1024,): 0.002}
        calls = []

        def bench(c):
            calls.append(c)
            return times[c]

        out = at.get_or_tune("k", "sig1", list(times), bench, (9,))
        assert out == (512,)
        assert sorted(calls) == sorted(times)
        # cache hit: no bench calls the second time
        calls.clear()
        assert at.get_or_tune("k", "sig1", list(times), bench,
                              (9,)) == (512,)
        assert calls == []
        # and the on-disk cache is a fresh process's warm start
        disk = json.loads(fresh_cache.read_text())
        key = [k for k in disk if "|sig1|" in k][0]
        # Key carries a kernel version + candidate-grid token so kernel
        # or grid changes self-invalidate stale entries (ADVICE r4).
        assert "|v1.g" in key
        assert disk[key]["blocks"] == [512]
        monkeypatch.setattr(at, "_mem", {})
        monkeypatch.setattr(at, "_loaded", False)
        assert at.get_or_tune("k", "sig1", list(times), bench,
                              (9,)) == (512,)
        assert calls == []

    def test_entry_of_an_older_kernel_version_is_not_read_back(
            self, fresh_cache, monkeypatch):
        """Blocks swept against the version-2 flash kernels (a forward that
        walks a cell sub-tile by sub-tile) say nothing about version 3: the
        sweep runs again and stores beside the stale entry."""
        import jax

        monkeypatch.setattr(at, "enabled", lambda: True)
        assert at._KERNEL_VERSIONS["flash_attention"] == 3
        cands = [(512, 512), (1024, 1024)]
        chip = getattr(jax.devices()[0], "device_kind", "tpu")
        stale = (f"flash_attention|{chip}|sigF|v2."
                 f"g{at._grid_token(cands)}")
        fresh_cache.write_text(json.dumps({stale: {"blocks": [512, 512]}}))
        calls = []

        def bench(c):
            calls.append(c)
            return {(512, 512): 0.002, (1024, 1024): 0.001}[c]

        assert at.get_or_tune("flash_attention", "sigF", cands, bench,
                              (1024, 1024)) == (1024, 1024)
        assert sorted(calls) == cands
        disk = json.loads(fresh_cache.read_text())
        assert disk[stale]["blocks"] == [512, 512]
        assert disk[stale.replace("|v2.", "|v3.")]["blocks"] == [1024, 1024]

    def test_failing_candidates_skipped(self, fresh_cache, monkeypatch):
        monkeypatch.setattr(at, "enabled", lambda: True)

        def bench(c):
            if c == (512,):
                raise RuntimeError("VMEM")
            return 0.002 if c == (256,) else 0.004

        out = at.get_or_tune("k", "sig2", [(256,), (512,), (1024,)],
                             bench, (9,))
        assert out == (256,)

    def test_all_failing_raises(self, fresh_cache, monkeypatch):
        """On the chip nothing catches a kernel failure and carries on
        with blocks nobody measured: the error names every candidate."""
        monkeypatch.setattr(at, "enabled", lambda: True)

        def bench(c):
            raise RuntimeError("scoped vmem")

        with pytest.raises(RuntimeError,
                           match=r"ALL 1 candidates failed(.|\n)*"
                                 r"\(1,\): RuntimeError: scoped vmem"):
            at.get_or_tune("k", "sig3", [(1,)], bench, (9,))
        # nothing cached: a later process may succeed where this one failed
        assert not fresh_cache.exists() or "sig3" not in \
            fresh_cache.read_text()

    def test_multiprocess_never_sweeps(self, fresh_cache, monkeypatch):
        import jax

        monkeypatch.setattr(at, "enabled", lambda: True)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(at, "_multihost_cache_ok", [False])
        calls = []
        cands = [(1,), (2,)]
        out = at.get_or_tune("k", "sig4", cands,
                             lambda c: calls.append(c) or 0.1, (9,))
        assert out == (9,) and calls == []  # no sweep in multi-host
        # A local cache hit is NOT trusted until the init-time
        # fingerprint agreement proved every host loaded the same cache
        # (ADVICE r4: per-host caches can legitimately differ ->
        # divergent XLA programs); until then, the default.
        chip = getattr(jax.devices()[0], "device_kind", "tpu")
        key = f"k|{chip}|sig4|v1.g{at._grid_token(cands)}"
        at._mem[key] = {"blocks": [2]}
        assert at.get_or_tune("k", "sig4", cands, lambda c: 0.1, (9,)) == (9,)
        # After verification, the (identical-everywhere) cache is used.
        monkeypatch.setattr(at, "_multihost_cache_ok", [True])
        assert at.get_or_tune("k", "sig4", cands, lambda c: 0.1, (9,)) == (2,)

    def test_verify_multihost_cache(self, fresh_cache, monkeypatch):
        import jax

        from horovod_tpu.ops import collective_ops as C
        from horovod_tpu.parallel import functions

        # Single process: trivially consistent.
        monkeypatch.setattr(at, "_multihost_cache_ok", [False])
        assert at.verify_multihost_cache() is True
        assert at._multihost_cache_ok[0]

        # Multi-host, agreement channel spans the world, fingerprints
        # agree -> trusted.
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(C, "_eager_world", lambda: 2)
        fp = at.cache_fingerprint()
        monkeypatch.setattr(functions, "allgather_object",
                            lambda obj: [fp, obj])
        assert at.verify_multihost_cache() is True

        # Fingerprints differ -> defaults (loud warning, no deadlock).
        monkeypatch.setattr(functions, "allgather_object",
                            lambda obj: ["other", obj])
        assert at.verify_multihost_cache() is False
        assert not at._multihost_cache_ok[0]

        # Agreement channel does not span the world -> not trusted.
        monkeypatch.setattr(C, "_eager_world", lambda: 1)
        assert at.verify_multihost_cache() is False


class TestTraceTimeSweep:
    def test_sweep_executes_under_an_active_jit_trace(self, fresh_cache,
                                                      monkeypatch):
        """The sweep fires while the caller's train step is being traced
        (block resolution happens inside flash_attention's forward). An
        ambient trace must not stage the bench's inner jits — r5 hardware
        sessions lost every candidate to TracerArrayConversionError this
        way. The worker-thread escape gives the bench a clean (thread-
        local) trace context, so real execution + host fetch works."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        monkeypatch.setattr(at, "enabled", lambda: True)
        swept = {}

        def traced(x):
            def bench(cand):
                # Real execution + concrete fetch, as _timed_chain does.
                y = jax.jit(lambda a: (a * cand[0]).sum())(
                    jnp.ones((8, 8), jnp.float32))
                return 1.0 / float(np.asarray(y))

            swept["blocks"] = at.get_or_tune(
                "k", "trace_sig", [(1,), (2,)], bench, (9,))
            return x * 1.0

        jax.jit(traced).lower(jnp.zeros((2, 2)))
        # (2,) is faster by construction (bench returns 1/(64*c)).
        assert swept["blocks"] == (2,)
        assert "trace_sig" in fresh_cache.read_text()

    def test_worker_inherits_callers_default_device(self, fresh_cache,
                                                    monkeypatch):
        """jax.default_device is thread-local; the sweep worker must
        carry the caller's pin so candidates are timed on the device the
        user chose, not device 0."""
        import jax

        monkeypatch.setattr(at, "enabled", lambda: True)
        pinned = jax.devices()[-1]
        seen = []

        def bench(cand):
            seen.append(jax.config.jax_default_device)
            return 0.001 * cand[0]

        with jax.default_device(pinned):
            out = at.get_or_tune("k", "devsig", [(1,), (2,)], bench, (9,))
        assert out == (1,)
        assert seen and all(d is pinned for d in seen)


@pytest.mark.parametrize("C", [768, 1024, 2048, 4096])
def test_xent_candidates_never_exceed_the_rule(C):
    """One source of what fits: the sweep looks at the C-derived default
    blocks and below, never above them."""
    from horovod_tpu.ops.flash_attention import _pick_block
    from horovod_tpu.ops.softmax_xent import _default_blocks

    default = _default_blocks(C)
    cands = at.xent_candidates(8192, 131072, default, _pick_block)
    assert default in cands
    assert all(128 <= bn <= default[0] and 128 <= bv <= default[1]
               for bn, bv in cands)


class TestShapeGates:
    def test_small_shapes_keep_defaults(self, fresh_cache, monkeypatch):
        """The B=1 model.init trace must not trigger a sweep."""
        monkeypatch.setattr(at, "enabled", lambda: True)
        import jax.numpy as jnp

        from horovod_tpu.ops.flash_attention import _pick_block

        out = at.flash_blocks(1, 1024, 1024, 12, 64, jnp.bfloat16, True,
                              (1024, 1024), _pick_block)
        assert out == (1024, 1024)
        out = at.xent_blocks(64, 1024, 128, jnp.float32, (1024, 1024),
                             _pick_block)
        assert out == (1024, 1024)
