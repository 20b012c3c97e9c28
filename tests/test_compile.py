"""Compile-once runtime tests (docs/compile.md): the persistent
executable cache, AOT warm pools, and background precompile for elastic
resizes.

Covers the contract surface the CI bricks lean on:
  * key anatomy — tag / wire-plan encoding / mesh geometry / shape+dtype
    signature each produce a DIFFERENT executable key (transfer safety:
    an executable compiled for one topology or plan never hits another);
  * hit ladder — miss compiles once; the second identical request is a
    memory hit; a fresh registry (new process) loads the entry from
    disk; a fresh PROCESS pays zero compiles (subprocess warm rerun —
    the scripts/compile_smoke.sh gate in miniature);
  * failure discipline — a corrupt index, a truncated payload, or a
    missing cache dir logs a warning and falls back to a cold compile
    (the cache is an optimization, never a failure);
  * resize ordering — ``ReplicaSet.request_resize`` keeps serving on the
    OLD geometry until the background warm-pool thread reports ready;
    only then does ``step_all`` drain and rebuild (drain-after-warm is
    the resize_stall_ms win);
  * observability — COMPILE:LOWER / COMPILE:COMPILE spans balance under
    the strict span audit; hits emit COMPILE:CACHE_HIT instants.
"""

import contextlib
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.compile import (
    CompileResult,
    arm_persistent_cache,
    cache as xcache,
    executable_key,
    get_or_compile,
    precompile,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N = 8


@contextlib.contextmanager
def _placed_cache(monkeypatch, path):
    """``JAX_COMPILATION_CACHE_DIR=path`` as a fresh process would see
    it: the variable for this repo's layers, jax's config for its own."""
    from jax.experimental.compilation_cache import compilation_cache as jcc

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", path)
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", path)
    jcc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        jcc.reset_cache()


@pytest.fixture()
def fresh_cache(tmp_path, monkeypatch):
    """Place the whole compile cache in an empty per-test directory and
    zero the process counters, restoring both afterwards."""
    monkeypatch.delenv("HOROVOD_COMPILE_CACHE", raising=False)
    # JAX took its directory from the variable at import, so the test —
    # not the code under test — moves JAX's own layer along with it: an
    # executable whose compile() was itself served from a session-shared
    # XLA disk cache can serialize into a payload that will not
    # deserialize in the same process, and these tests pin the
    # clean-layer hit ladder.
    with _placed_cache(monkeypatch, str(tmp_path)):
        xcache.clear_memory()
        xcache.reset_stats()
        yield tmp_path
    xcache.clear_memory()
    xcache.reset_stats()


def _lower_double(shape=(8,)):
    f = jax.jit(lambda x: x * 2.0 + 1.0)
    spec = jax.ShapeDtypeStruct(shape, jnp.float32)
    return lambda: f.lower(spec)


# ---------------------------------------------------------------------------
# key anatomy
# ---------------------------------------------------------------------------


class TestExecutableKey:
    def test_key_is_stable(self):
        spec = (jax.ShapeDtypeStruct((4, 8), jnp.float32),)
        assert executable_key("t", plan="p", shapes=spec) == \
            executable_key("t", plan="p", shapes=spec)

    def test_key_carries_tag_plan_and_jax_version(self):
        k = executable_key("stepfn", plan="z3|ov1")
        assert "stepfn" in k and "z3|ov1" in k
        assert f"jax{jax.__version__}" in k

    def test_tag_sensitivity(self):
        assert executable_key("a") != executable_key("b")

    def test_plan_sensitivity(self):
        assert executable_key("t", plan="z1") != \
            executable_key("t", plan="z3")

    def test_shape_dtype_sensitivity(self):
        s32 = (jax.ShapeDtypeStruct((4, 8), jnp.float32),)
        s16 = (jax.ShapeDtypeStruct((4, 8), jnp.bfloat16),)
        s_wide = (jax.ShapeDtypeStruct((4, 16), jnp.float32),)
        keys = {executable_key("t", shapes=s)
                for s in (s32, s16, s_wide)}
        assert len(keys) == 3

    def test_mesh_geometry_sensitivity(self):
        devs = jax.devices()
        m4 = jax.sharding.Mesh(np.array(devs[:4]), ("serve_tp",))
        m8 = jax.sharding.Mesh(np.array(devs[:8]), ("serve_tp",))
        m4b = jax.sharding.Mesh(np.array(devs[4:8]), ("serve_tp",))
        keys = {executable_key("t", mesh=m) for m in (m4, m8, m4b)}
        # Different world sizes AND different device slices of the same
        # size are different executables (a replica's engine is pinned
        # to its device group).
        assert len(keys) == 3

    def test_framework_mesh_uses_geometry_fingerprint(self):
        from horovod_tpu.common import basics

        k = executable_key("t", mesh=hvd.mesh())
        assert basics.mesh_geometry() in k


# ---------------------------------------------------------------------------
# hit ladder: miss -> memory -> disk -> warm process
# ---------------------------------------------------------------------------


class TestHitLadder:
    def test_miss_then_memory_hit(self, fresh_cache):
        r1 = get_or_compile("t_ladder", _lower_double())
        assert isinstance(r1, CompileResult)
        assert r1.source == "compiled" and not r1.cache_hit
        assert r1.compile_ms > 0
        r2 = get_or_compile("t_ladder", _lower_double())
        assert r2.source == "memory" and r2.cache_hit
        assert r2.key == r1.key
        s = xcache.stats()
        assert s["misses"] == 1 and s["hits"] == 1
        assert xcache.compile_count() == 1
        x = jnp.arange(8, dtype=jnp.float32)
        np.testing.assert_allclose(r2.compiled(x), x * 2 + 1)

    def test_disk_hit_after_registry_clear(self, fresh_cache):
        r1 = get_or_compile("t_disk", _lower_double(),
                            aux_fn=lambda lowered: {"bytes": 123})
        assert r1.source == "compiled" and r1.aux == {"bytes": 123}
        xcache.clear_memory()
        r2 = get_or_compile("t_disk", _lower_double())
        assert r2.source == "disk" and r2.cache_hit
        # aux rides the disk entry: warm hits replay the metadata the
        # miss captured at trace time (bench's wire-stats pattern).
        assert r2.aux == {"bytes": 123}
        assert xcache.stats()["disk_hits"] == 1
        x = jnp.ones((8,), jnp.float32)
        np.testing.assert_allclose(r2.compiled(x), x * 2 + 1)

    def test_lower_not_called_on_hit(self, fresh_cache):
        calls = []

        def lower():
            calls.append(1)
            return _lower_double()()

        get_or_compile("t_lazy", lower)
        get_or_compile("t_lazy", lower)
        xcache.clear_memory()
        get_or_compile("t_lazy", lower)
        assert len(calls) == 1  # memory AND disk hits skip lowering

    def test_distinct_shapes_do_not_alias(self, fresh_cache):
        f = jax.jit(lambda x: x + 1.0)
        r8 = get_or_compile(
            "t_shape", lambda: f.lower(
                jax.ShapeDtypeStruct((8,), jnp.float32)),
            shapes=(jax.ShapeDtypeStruct((8,), jnp.float32),))
        r4 = get_or_compile(
            "t_shape", lambda: f.lower(
                jax.ShapeDtypeStruct((4,), jnp.float32)),
            shapes=(jax.ShapeDtypeStruct((4,), jnp.float32),))
        assert r8.key != r4.key
        assert r4.source == "compiled"
        np.testing.assert_allclose(
            r4.compiled(jnp.zeros((4,), jnp.float32)), np.ones((4,)))

    def test_persistence_disabled_keeps_memory_layer(
            self, fresh_cache, monkeypatch):
        monkeypatch.setenv("HOROVOD_COMPILE_CACHE", "0")
        r1 = get_or_compile("t_off", _lower_double())
        assert r1.source == "compiled"
        assert get_or_compile("t_off", _lower_double()).source == "memory"
        # nothing persisted: a fresh registry compiles again
        xcache.clear_memory()
        assert get_or_compile("t_off", _lower_double()).source == \
            "compiled"
        assert not os.path.exists(
            os.path.join(str(fresh_cache), "exec", "index.json"))

    def test_warm_process_pays_zero_compiles(self, fresh_cache):
        """The compile_smoke.sh contract in miniature: a second PROCESS
        with the same cache dir serves its executable from disk —
        compile_count == 0."""
        script = (
            "import json, os\n"
            "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
            "import jax, jax.numpy as jnp\n"
            "from horovod_tpu.compile import cache\n"
            "f = jax.jit(lambda x: x * 2.0 + 1.0)\n"
            "spec = jax.ShapeDtypeStruct((8,), jnp.float32)\n"
            "res = cache.get_or_compile('t_warm_proc',"
            " lambda: f.lower(spec))\n"
            "out = res.compiled(jnp.arange(8, dtype=jnp.float32))\n"
            "print(json.dumps({'source': res.source,"
            " 'compile_count': cache.compile_count(),"
            " 'stats': cache.stats(), 'y3': float(out[3])}))\n")
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_COMPILATION_CACHE_DIR"] = str(fresh_cache)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

        def run():
            proc = subprocess.run([sys.executable, "-c", script],
                                  env=env, capture_output=True,
                                  text=True, timeout=300)
            assert proc.returncode == 0, proc.stderr[-4000:]
            return json.loads(proc.stdout.strip().splitlines()[-1])

        cold = run()
        assert cold["source"] == "compiled"
        assert cold["compile_count"] == 1
        warm = run()
        assert warm["source"] == "disk", warm
        assert warm["compile_count"] == 0
        assert warm["stats"]["disk_hits"] == 1
        assert warm["y3"] == cold["y3"] == 7.0


# ---------------------------------------------------------------------------
# failure discipline: the cache is an optimization, never a failure
# ---------------------------------------------------------------------------


class TestCorruptCacheTolerance:
    def test_corrupt_index_falls_back_to_cold_compile(self, fresh_cache):
        idx = os.path.join(str(fresh_cache), "exec", "index.json")
        os.makedirs(os.path.dirname(idx), exist_ok=True)
        with open(idx, "w") as f:
            f.write("{not json at all")
        r = get_or_compile("t_corrupt_idx", _lower_double())
        assert r.source == "compiled"
        x = jnp.zeros((8,), jnp.float32)
        np.testing.assert_allclose(r.compiled(x), np.ones((8,)))
        # and the store path healed the index for the next reader
        xcache.clear_memory()
        assert get_or_compile("t_corrupt_idx",
                              _lower_double()).source == "disk"

    def test_truncated_payload_logs_and_recompiles(self, fresh_cache,
                                                   caplog):
        get_or_compile("t_trunc", _lower_double())
        exec_dir = os.path.join(str(fresh_cache), "exec")
        with open(os.path.join(exec_dir, "index.json")) as f:
            meta = next(iter(json.load(f).values()))
        with open(os.path.join(exec_dir, meta["file"]), "wb") as f:
            f.write(b"\x80garbage")
        xcache.clear_memory()
        xcache._warned["disk"] = False
        import logging

        with caplog.at_level(logging.WARNING, "horovod_tpu.compile"):
            r = get_or_compile("t_trunc", _lower_double())
        assert r.source == "compiled"  # cold compile, not an exception
        assert any("falling back to cold compile" in m
                   for m in caplog.messages)

    def test_unwritable_cache_dir_still_compiles(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           "/proc/definitely/not/writable")
        xcache.clear_memory()
        r = get_or_compile("t_nodir", _lower_double())
        assert r.source == "compiled"
        np.testing.assert_allclose(
            r.compiled(jnp.zeros((8,), jnp.float32)), np.ones((8,)))
        xcache.clear_memory()


# ---------------------------------------------------------------------------
# placement: where the cache lives, and which devices a disk hit lands on
# ---------------------------------------------------------------------------


class TestPlacement:
    @pytest.fixture()
    def config_updates(self, monkeypatch):
        """Record jax.config.update calls instead of applying them, and
        keep arming from creating directories."""
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: calls.append((k, v)))
        monkeypatch.setattr(os, "makedirs", lambda *a, **k: None)
        return calls

    def test_env_set_names_no_other_dir(self, tmp_path, monkeypatch,
                                        config_updates):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert arm_persistent_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in dict(config_updates)
        # both layers (and the kernel block choices) live under it
        from horovod_tpu.ops import kernel_autotune

        monkeypatch.delenv("HOROVOD_AUTOTUNE_CACHE", raising=False)
        assert xcache._index_path() == str(
            tmp_path / "exec" / "index.json")
        assert kernel_autotune._cache_path() == str(
            tmp_path / "kernel_autotune.json")

    def test_env_unset_is_the_fixed_checkout_dir(self, monkeypatch,
                                                 config_updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(REPO, ".compile_cache")
        assert arm_persistent_cache() == fixed
        assert dict(config_updates)["jax_compilation_cache_dir"] == fixed
        assert xcache._exec_dir() == os.path.join(fixed, "exec")

    def test_env_unset_dir_is_identical_across_processes(self):
        """Not a temp dir, a pid or the time: two processes agree, and
        neither looks at the home directory."""
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env["HOME"] = "/nonexistent-home"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        script = ("from horovod_tpu.compile import cache_dir; "
                  "print(cache_dir())")
        seen = [subprocess.run([sys.executable, "-c", script], env=env,
                               capture_output=True, text=True,
                               timeout=300, check=True).stdout.strip()
                for _ in range(2)]
        assert seen == [os.path.join(REPO, ".compile_cache")] * 2

    def test_one_device_mesh_never_hits_an_eight_device_entry(
            self, fresh_cache):
        """The scaling-sweep failure: on an 8-device host, a 1-chip leg
        must neither take the 8-chip entry nor be loaded onto all 8."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from horovod_tpu.common import basics

        f = jax.jit(lambda x: x * 2.0 + 1.0)

        def get(mesh):
            spec = jax.ShapeDtypeStruct(
                (8,), jnp.float32, sharding=NamedSharding(mesh, P()))
            return get_or_compile("t_subset", lambda: f.lower(spec),
                                  mesh=mesh, shapes=(spec,))

        devs = jax.devices()
        m8 = basics._build_mesh(devs[:N], (2, 4))
        # not device 0: the loader's default would put it there
        m1 = basics._build_mesh(devs[3:4], (1, 1))
        r8 = get(m8)
        assert r8.source == "compiled"
        xcache.clear_memory()
        r1 = get(m1)
        assert r1.key != r8.key and r1.source == "compiled"
        xcache.clear_memory()
        again = get(m1)
        assert again.source == "disk"
        x = jax.device_put(jnp.ones((8,), jnp.float32),
                           NamedSharding(m1, P()))
        out = again.compiled(x)
        assert out.devices() == {devs[3]}
        np.testing.assert_allclose(out, 3.0)
        assert get(m8).source == "disk"


# ---------------------------------------------------------------------------
# arm_persistent_cache + hvd.precompile
# ---------------------------------------------------------------------------


class TestArmAndPrecompile:
    def test_arm_uses_the_placed_dir_for_both_layers(self, fresh_cache):
        armed = arm_persistent_cache()
        assert armed == str(fresh_cache) == xcache.cache_dir()
        assert os.path.isdir(armed)
        assert jax.config.jax_compilation_cache_dir == armed
        assert xcache._exec_dir() == os.path.join(armed, "exec")

    def test_arm_respects_disable_knob(self, fresh_cache, monkeypatch):
        monkeypatch.setenv("HOROVOD_COMPILE_CACHE", "0")
        assert arm_persistent_cache() is None

    def test_precompile_warms_every_spec_once(self, fresh_cache):
        specs = [(jax.ShapeDtypeStruct((4,), jnp.float32),),
                 (jax.ShapeDtypeStruct((16,), jnp.float32),)]
        out = hvd.precompile(lambda x: x - 1.0, specs, tag="t_pool")
        assert [r.source for r in out] == ["compiled", "compiled"]
        np.testing.assert_allclose(
            out[1].compiled(jnp.ones((16,), jnp.float32)),
            np.zeros((16,)))
        # the warm pool dedupes: same specs again -> all hits
        again = precompile(lambda x: x - 1.0, specs, tag="t_pool")
        assert all(r.cache_hit for r in again)
        assert xcache.compile_count() == 2


# ---------------------------------------------------------------------------
# background precompile before the resize drain (serve)
# ---------------------------------------------------------------------------


@pytest.mark.serve
class TestResizePrecompileOrdering:
    @pytest.fixture(scope="class")
    def serve_bits(self):
        from horovod_tpu.models import GPT, gpt_tiny
        from horovod_tpu.serve import PageConfig

        cfg = gpt_tiny(dtype=jnp.float32, num_heads=8)
        params = GPT(cfg).init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 8), jnp.int32))["params"]
        pc = PageConfig(num_pages=64, page_size=4, max_slots=4,
                        pages_per_slot=16, num_layers=cfg.num_layers,
                        num_heads=cfg.num_heads,
                        head_dim=cfg.d_model // cfg.num_heads)
        return cfg, params, pc

    def test_drain_waits_for_background_warm_pool(self, serve_bits,
                                                  fresh_cache):
        from horovod_tpu.serve import ReplicaSet, Request

        cfg, params, pc = serve_bits
        rset = ReplicaSet(cfg, params, pc, n_replicas=2, eos_id=1)
        for i in range(3):
            rset.submit(Request(req_id=i, prompt=[2, 3, 4, 5],
                                max_new_tokens=4, arrival_time=0.0))
        assert rset.request_resize(1)
        assert rset.resize_pending
        # a second request while one is pending is refused
        assert not rset.request_resize(2)
        # the old geometry keeps serving while the target warms: the
        # engine set must NOT shrink until the warm pool reports ready
        saw_old_geometry_step = False
        deadline = time.monotonic() + 120.0
        step = 0
        while rset.resize_pending:
            if len(rset.engines) == 2:
                saw_old_geometry_step = True
            rset.step_all(float(step))
            step += 1
            assert time.monotonic() < deadline, \
                "background precompile never completed"
        assert saw_old_geometry_step
        assert len(rset.engines) == 1
        ev = rset.resize_events[-1]
        assert ev["background"] is True
        assert ev["to"] == 1 and ev["from"] == 2
        # ordering contract: the warm pool ran BEFORE the drain, so the
        # stall window excludes it — precompile_ms is accounted
        # separately and the event says the rebuild was not warm-blocking
        assert ev["precompile_ms"] > 0
        assert ev["resize_stall_ms"] >= 0
        # in-flight work survived the flip
        while rset.has_work and time.monotonic() < deadline:
            rset.step_all(float(step))
            step += 1
        done = len(rset.stats.completed) + sum(
            len(e.stats.completed) for e in rset.engines)
        assert done == 3

    def test_foreground_resize_warms_before_drain(self, serve_bits,
                                                  fresh_cache):
        from horovod_tpu.serve import ReplicaSet

        cfg, params, pc = serve_bits
        rset = ReplicaSet(cfg, params, pc, n_replicas=2, eos_id=1)
        xcache.reset_stats()
        rset.resize(1)
        ev = rset.resize_events[-1]
        assert ev["warm"] is True and ev["background"] is False
        assert ev["precompile_ms"] > 0
        from horovod_tpu import monitor

        g = monitor.metrics().gauge("serve.resize_stall_ms").value
        # the event value is rounded to 3 decimals; the gauge is raw
        assert g == pytest.approx(ev["resize_stall_ms"], abs=1e-3)


# ---------------------------------------------------------------------------
# observability: strict span balance
# ---------------------------------------------------------------------------


class TestCompileSpans:
    def test_compile_spans_balance_strict(self, tmp_path, monkeypatch):
        from horovod_tpu.monitor import span_audit

        tl = str(tmp_path / "compile_tl.json")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "cc"))
        hvd.shutdown()
        os.environ["HOROVOD_TIMELINE"] = tl
        try:
            hvd.init(devices=jax.devices())
            xcache.clear_memory()
            xcache.reset_stats()
            get_or_compile("t_span", _lower_double())
            get_or_compile("t_span", _lower_double())  # CACHE_HIT instant
        finally:
            del os.environ["HOROVOD_TIMELINE"]
            hvd.shutdown()
            hvd.init(devices=jax.devices())
            xcache.clear_memory()
        audit = span_audit.audit_spans(tl, prefix="COMPILE:",
                                       require_balanced=True,
                                       require_spans=True, strict=True)
        assert audit.count.get("COMPILE:LOWER", 0) == 1
        assert audit.count.get("COMPILE:COMPILE", 0) == 1
        events = span_audit.load_events(tl)
        hits = [e for e in events
                if e.get("name") == "COMPILE:CACHE_HIT"]
        assert len(hits) == 1 and hits[0].get("ph") == "i"

    def test_compile_is_a_known_span_prefix(self):
        from horovod_tpu.monitor.span_audit import KNOWN_PREFIXES

        assert "COMPILE" in KNOWN_PREFIXES

    def test_miss_records_compile_straggler_phase_and_metrics(
            self, fresh_cache):
        from horovod_tpu import monitor

        m0 = monitor.metrics().counter("compile.misses",
                                       key="t_metrics").value
        get_or_compile("t_metrics", _lower_double())
        get_or_compile("t_metrics", _lower_double())
        assert monitor.metrics().counter(
            "compile.misses", key="t_metrics").value == m0 + 1
        assert monitor.metrics().counter(
            "compile.hits", key="t_metrics").value >= 1
