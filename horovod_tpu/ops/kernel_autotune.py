"""One-shot per-(kernel, shape, chip) Pallas block-size autotuning.

The reference autotunes its performance knobs (fusion threshold, cycle
time) with a Bayesian ParameterManager (horovod/common/optim/ — this
repo's native counterpart is cc/src/parameter_manager.cc + gp.cc). On
TPU, the knobs that matter most are the Pallas kernel block sizes: the
flash-attention grid block alone is worth 2x on an attention call (1024
vs 512 on a v5e, PERF.md PR 25). This module folds those knobs into an
autotune pass:

* first use of a kernel at a new (shape, dtype, chip) sweeps a small
  candidate grid — each candidate timed as a jitted ``lax.scan`` chain of
  fwd+bwd applications so the device runs a contiguous multi-hundred-ms
  batch and per-dispatch overhead vanishes from the comparison;
* the winner lands in an on-disk JSON cache (``HOROVOD_AUTOTUNE_CACHE``,
  default ``kernel_autotune.json`` under the compile-cache root,
  compile/cache.py — the picked blocks are part of what is compiled)
  keyed like the reference's autotune log — kernel kind, chip kind,
  shape signature — so every later process skips straight to it;
* explicit ``block_*`` arguments and the ``HOROVOD_FLASH_BLOCK_Q/K`` /
  ``HOROVOD_XENT_BLOCK_N/V`` env knobs always win over the autotuner,
  and off-TPU (interpreter-mode tests) the hand-tuned defaults are used
  untouched. ``HOROVOD_KERNEL_AUTOTUNE=0`` disables the sweep entirely;
* a candidate the compiler refuses is skipped, but a sweep in which NO
  candidate can be timed raises: on the chip nothing may catch a kernel
  failure and carry on with blocks nobody measured.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

_lock = threading.Lock()
_mem: Dict[str, dict] = {}
_loaded = False

# Bump when a kernel's implementation changes in a way that invalidates
# previously-tuned block choices (ADVICE r4: stale cache entries were
# returned before any legality/sweep logic runs). The candidate grid is
# additionally hashed into the key, so grid edits self-invalidate.
_KERNEL_VERSIONS: Dict[str, int] = {
    "flash_attention": 3,   # 2: sub-tiles inside the grid cell (PR 25);
                            # 3: the forward walks by strips (PR 42)
    "linear_xent": 1,
    "selective_scan": 2,   # 2: the backward a chunk of all channels (PR 40)
}


def _grid_token(candidates: Sequence[Tuple[int, ...]]) -> str:
    import hashlib

    return hashlib.md5(
        repr(sorted(tuple(c) for c in candidates)).encode()
    ).hexdigest()[:8]


def _cache_path() -> str:
    from ..compile.cache import cache_dir

    return os.environ.get("HOROVOD_AUTOTUNE_CACHE") or os.path.join(
        cache_dir(), "kernel_autotune.json")


def enabled() -> bool:
    from ..common.config import _env_bool

    if not _env_bool("HOROVOD_KERNEL_AUTOTUNE", True):
        return False
    import jax

    return jax.default_backend() == "tpu"


def _load_locked() -> None:
    global _loaded
    if _loaded:
        return
    _loaded = True
    try:
        with open(_cache_path()) as f:
            _mem.update(json.load(f))
    except (OSError, json.JSONDecodeError, ValueError):
        pass  # cache is an optimization, never a failure


def _store_locked(key: str, entry: dict) -> None:
    _mem[key] = entry
    path = _cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # Read-merge-write under an OS lock: concurrent processes tuning
        # different shapes must not clobber each other's entries.
        import fcntl

        with open(path + ".lock", "w") as lockf:
            fcntl.flock(lockf, fcntl.LOCK_EX)
            disk: dict = {}
            try:
                with open(path) as f:
                    disk = json.load(f)
            except (FileNotFoundError, json.JSONDecodeError):
                pass
            disk[key] = entry
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(disk, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
    except OSError as e:  # cache is an optimization, never a failure
        logging.debug("autotune cache write failed: %s", e)


def cache_lookup(key: str) -> Optional[dict]:
    """Entry stored under ``key`` in the shared autotune cache file
    (``HOROVOD_AUTOTUNE_CACHE``), or None. Used by the collective-knob
    autotuner (autotune/driver.py) so kernel block choices and frozen
    collective tunables live in ONE warm-start file with the same
    locking, atomicity, and multi-host fingerprint discipline."""
    with _lock:
        _load_locked()
        entry = _mem.get(key)
    return entry if isinstance(entry, dict) else None


def cache_store(key: str, entry: dict) -> None:
    """Persist ``entry`` under ``key`` in the shared autotune cache file
    (read-merge-write under the OS lock; see :func:`cache_lookup`)."""
    with _lock:
        _store_locked(key, entry)


def get_or_tune(kind: str, sig: str,
                candidates: Sequence[Tuple[int, ...]],
                bench: Callable[[Tuple[int, ...]], float],
                default: Tuple[int, ...]) -> Tuple[int, ...]:
    """The cached best candidate for (kind, chip, sig), sweeping once if
    unseen. ``bench(candidate)`` returns seconds per application (lower
    is better) or raises — failing candidates are skipped. Returns
    ``default`` when disabled or off-TPU; raises ``RuntimeError`` when
    every candidate fails."""
    if not enabled():
        return default
    import jax

    chip = getattr(jax.devices()[0], "device_kind", "tpu")
    ver = _KERNEL_VERSIONS.get(kind, 1)
    key = f"{kind}|{chip}|{sig}|v{ver}.g{_grid_token(candidates)}"
    with _lock:
        _load_locked()
        hit = _mem.get(key)
    cached = tuple(hit["blocks"]) if (
        isinstance(hit, dict) and isinstance(hit.get("blocks"), list)
    ) else None
    if jax.process_count() > 1:
        # Multi-host SPMD must compile IDENTICAL programs on every host.
        # Per-host cache files can legitimately differ (one host tuned,
        # another not), so a local cache hit is only trusted after the
        # init-time fingerprint agreement proved every host loaded the
        # same cache (verify_multihost_cache); otherwise every host
        # falls back to the (identical-by-construction) default. No
        # collective runs here — a hot-path collective gated on
        # host-local state could deadlock divergent hosts.
        if _multihost_cache_ok[0] and cached is not None:
            return cached
        return default
    if cached is not None:
        return cached

    results: List[Tuple[float, Tuple[int, ...]]] = []
    errors: List[str] = []
    t_sweep = time.perf_counter()

    def _sweep() -> None:
        for cand in candidates:
            try:
                dt = bench(cand)
                results.append((dt, cand))
            except Exception as e:  # compile/VMEM failure: candidate illegal
                errors.append(f"{cand}: {type(e).__name__}: {str(e)[:200]}")
                logging.info("autotune %s %s: candidate %s failed (%s)",
                             kind, sig, cand, str(e)[:200])

    # The sweep fires at TRACE time (kernels resolve their blocks while
    # the caller's train step is being traced), and under an ambient jit
    # trace the bench's inner jit calls would be STAGED into that trace
    # instead of executed — the host fetch then hits a tracer and every
    # candidate dies with TracerArrayConversionError (the r5 hardware
    # sessions' silent all-candidates failure). JAX's trace state is
    # thread-local, so a worker thread has a clean trace context while
    # sharing the initialized device client: real compile + execute +
    # timing, regardless of the caller's trace depth.
    # jax context managers (default_device & co) are thread-local: carry
    # the caller's effective default device into the worker so the bench
    # times the device the caller pinned, not whatever device 0 is doing.
    # Anything escaping _sweep's per-candidate try (it only catches
    # Exception) re-raises in the caller — a bare Thread would hand it to
    # threading.excepthook and the empty-results path would then lie
    # ("ALL candidates failed" with no errors).
    caller_device = jax.config.jax_default_device
    escaped: List[BaseException] = []

    def _sweep_with_context() -> None:
        try:
            if caller_device is None:
                _sweep()
            else:
                with jax.default_device(caller_device):
                    _sweep()
        except BaseException as e:
            escaped.append(e)

    worker = threading.Thread(target=_sweep_with_context,
                              name="hvd-autotune")
    worker.start()
    worker.join()
    if escaped:
        raise escaped[0]
    if not results:
        # Every candidate failing is not a per-candidate legality quirk —
        # it is the sweep (or the kernel itself) not working on this
        # chip. The default blocks are among the candidates, so carrying
        # on with them would only move the failure into the caller's
        # compile, minus the evidence.
        raise RuntimeError(
            f"horovod_tpu autotune: {kind} {sig} — ALL "
            f"{len(candidates)} candidates failed (set "
            f"HOROVOD_KERNEL_AUTOTUNE=0 to run the default blocks "
            f"{default} unswept). Errors:\n  " + "\n  ".join(errors))
    results.sort()
    best_dt, best = results[0]
    entry = {"blocks": list(best), "seconds_per_call": best_dt,
             "sweep_seconds": round(time.perf_counter() - t_sweep, 1),
             "results": [{"blocks": list(c), "seconds": round(d, 6)}
                         for d, c in results]}
    with _lock:
        _store_locked(key, entry)
    logging.warning(
        "horovod_tpu autotune: %s %s -> blocks %s (%.3f ms/call; swept %d "
        "candidates in %.0fs; cached in %s)", kind, sig, best,
        best_dt * 1e3, len(results), entry["sweep_seconds"], _cache_path())
    return best


# Multi-host cache trust: set once by verify_multihost_cache() at init
# time. Until it runs (and proves every host loaded an identical cache
# file), multi-host get_or_tune uses only the defaults.
_multihost_cache_ok = [False]


def cache_fingerprint() -> str:
    """Canonical digest of the loaded autotune cache."""
    import hashlib

    with _lock:
        _load_locked()
        blob = json.dumps(_mem, sort_keys=True)
    return hashlib.md5(blob.encode()).hexdigest()


def verify_multihost_cache() -> bool:
    """One-shot init-time agreement: allgather the cache fingerprint
    across the process world; local cache hits are trusted in multi-host
    mode only if every host loaded the same cache file (ADVICE r4:
    divergent per-host caches compile divergent XLA programs — a
    hang/garbage risk in SPMD).

    Called from ``hvd.init()`` — the one point where every process is
    guaranteed in lockstep, so the collective cannot deadlock divergent
    hosts the way a lazy hot-path agreement could. Returns the verdict
    (also stored module-globally for get_or_tune)."""
    import jax

    if jax.process_count() <= 1:
        _multihost_cache_ok[0] = True  # single host: nothing to diverge
        return True
    try:
        from ..ops import collective_ops as C
        from ..parallel.functions import allgather_object

        # The allgather must actually span every jax process, or the
        # "agreement" is vacuous.
        if C._eager_world() < jax.process_count():
            logging.info(
                "autotune: eager agreement channel spans %d < %d jax "
                "processes; cannot verify cache consistency",
                C._eager_world(), jax.process_count())
            ok = False
        else:
            prints = allgather_object(cache_fingerprint())
            ok = len(set(prints)) == 1
    except Exception as e:  # no agreement channel: defaults are safe
        logging.info("autotune multi-host cache verification unavailable "
                     "(%s); using default blocks", e)
        ok = False
    if not ok:
        logging.warning(
            "horovod_tpu autotune: per-host kernel caches differ (or "
            "could not be verified); multi-host runs will use default "
            "block sizes. Ship one HOROVOD_AUTOTUNE_CACHE file to every "
            "host to enable tuned blocks.")
    _multihost_cache_ok[0] = ok
    return ok


def _timed_chain(step_fn, args, target_seconds: float = 0.25,
                 max_chain: int = 16384,
                 chain: Optional[int] = None) -> Tuple[float, int]:
    """Seconds per application of ``step_fn``, measured as a jitted
    ``lax.scan`` chain (contiguous device work; iterations serialized
    through the carry so nothing is DCE'd or overlapped away).

    The chain length grows geometrically until one call costs >=
    ``target_seconds`` of wall clock around ``block_until_ready``, so
    the fixed per-dispatch cost is a small share of what is compared.
    Returns (seconds_per_call, chain_used); pass ``chain`` to skip the
    growth calibration (reusing the first candidate's calibration keeps
    a sweep at one compile per candidate)."""
    import jax
    from jax import lax

    def make(chain):
        def many(carry, *rest):
            def body(c, _):
                return step_fn(c, *rest), None

            out, _ = lax.scan(body, carry, None, length=chain)
            return out

        f = jax.jit(many)
        jax.block_until_ready(f(*args))  # compile + warm
        return f

    def timed(f):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        return time.perf_counter() - t0

    if chain is None:
        chain = 16
        while True:
            f = make(chain)
            t = min(timed(f), timed(f))
            if t >= target_seconds or chain >= max_chain:
                break
            grow = max(2, min(16, int(target_seconds / max(t, 1e-4)) + 1))
            chain = min(max_chain, chain * grow)
    else:
        f = make(chain)
        t = min(timed(f), timed(f))
    return t / chain, chain


def flash_blocks(B: int, Tq: int, Tk: int, H: int, D: int, dtype,
                 causal: bool, default: Tuple[int, int],
                 pick_block, window: Optional[int] = None,
                 kv_heads: Optional[int] = None,
                 block_diffusion: Optional[int] = None) -> Tuple[int, int]:
    """Autotuned (block_q, block_k) for a flash-attention shape. A window
    and grouped KV heads (``kv_heads`` < ``H``) are part of the shape: they
    key the cache and the timed call has them. A windowed call's blocks are
    square, so only square candidates are timed. For such a shape no
    candidate is above the hand-tuned default and the whole backward is
    timed (dk, dv too); so it is for a block-diffusion call (``Tq`` its
    ``2 L`` rows; the blocks divide a half). A (1024, 2048) blocking won the forward-and-dq
    sweep of the full grouped call at T = 8192 by 5% and its dk/dv kernel
    then overflowed scoped VMEM inside the step (PERF.md, PR 30), which a
    sweep standing alone cannot see. Ungrouped calls without a window keep
    the candidates and the timed call they had."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sig = f"B{B}.Tq{Tq}.Tk{Tk}.H{H}.D{D}.{jnp.dtype(dtype).name}" \
          f".{'c' if causal else 'f'}"
    if window is not None:
        sig += f".w{window}"
    if kv_heads is not None:
        sig += f".kv{kv_heads}"
    if block_diffusion is not None:
        sig += f".bd{block_diffusion}"

    # Too-small workloads (e.g. the B=1 model.init trace) neither benefit
    # from tuning nor time reliably — keep the default, don't sweep.
    if 4.0 * B * H * Tq * Tk * D < 1e10:
        return default

    # Candidate grid, deduplicated by the EFFECTIVE blocking after the
    # legality shrink (different preferences can collapse to one choice).
    # Grouped or windowed: the whole backward, nothing above the default.
    square = window is not None or block_diffusion is not None
    bounded = square or kv_heads is not None
    grid = [(bq, bk) for bq in (512, 1024, 2048) for bk in (512, 1024,
                                                            2048)
            if (not square or bq == bk)
            and not (bounded and max(bq, bk) > max(default))]
    halves = 1 if block_diffusion is None else 2
    seen, cands = set(), []
    for bq, bk in grid:
        eff = (pick_block(Tq // halves, bq), pick_block(Tk // halves, bk))
        if None in eff or eff in seen:
            continue
        seen.add(eff)
        cands.append((bq, bk))
    if len(cands) <= 1:
        return default

    cal = {"chain": None}  # calibrate once, reuse across candidates

    def bench(cand):
        bq, bk = cand
        from .flash_attention import flash_attention

        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(B, Tq, H, D), dtype) * 0.3
        k = jnp.asarray(rs.randn(B, Tk, kv_heads or H, D), dtype) * 0.3
        v = jnp.asarray(rs.randn(B, Tk, kv_heads or H, D), dtype) * 0.3

        def step(q, k, v):
            g = jax.grad(lambda q, k, v: flash_attention(
                q, k, v, causal=causal, window=window, block_q=bq,
                block_k=bk, block_diffusion=block_diffusion,
            ).astype(jnp.float32).sum(),
                argnums=(0, 1, 2) if bounded else 0)(q, k, v)
            if bounded:   # dk, dv [.., Hkv, D] into the carry as well
                g = g[0] + sum(jnp.repeat(x, H // x.shape[2], axis=2)
                               for x in g[1:])
            # Couple the carry to the grad with a small NON-ZERO factor:
            # a 0.0 coupling is constant-folded and the whole chain DCE'd
            # into a no-op (measured: 0.000 ms "kernels").
            return q + (1e-8 * g).astype(q.dtype)

        dt, cal["chain"] = _timed_chain(step, (q, k, v),
                                        chain=cal["chain"])
        return dt

    return get_or_tune("flash_attention", sig, cands, bench, default)


def xent_candidates(N: int, V: int, default: Tuple[int, int],
                    pick_block) -> List[Tuple[int, int]]:
    """The (block_n, block_v) preferences a sweep may time: ``default`` —
    ``softmax_xent._default_blocks(C)``, the largest blocks whose backward
    the compiler accepts at this hidden width — and the half of either
    block, deduplicated by the blocking they snap to on (N, V). Nothing
    above the rule: a larger block can win standalone and then overflow
    scoped VMEM inside a full train step's fusion context, which a
    standalone sweep cannot see."""
    def with_half(b):
        return (max(128, b // 2 // 128 * 128), b)

    seen, cands = set(), []
    for bn in with_half(default[0]):
        for bv in with_half(default[1]):
            eff = (pick_block(N, bn), pick_block(V, bv))
            if None in eff or eff in seen:
                continue
            seen.add(eff)
            cands.append((bn, bv))
    return cands


def xent_blocks(N: int, V: int, C: int, dtype,
                default: Tuple[int, int], pick_block) -> Tuple[int, int]:
    """Autotuned (block_n, block_v) for the fused linear cross-entropy,
    among :func:`xent_candidates` of ``default``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sig = f"N{N}.V{V}.C{C}.{jnp.dtype(dtype).name}"
    if 6.0 * N * V * C < 1e10:  # tiny head: don't sweep (see flash gate)
        return default
    cands = xent_candidates(N, V, default, pick_block)
    if len(cands) <= 1:
        return default

    cal = {"chain": None}

    def bench(cand):
        bn, bv = cand
        from .softmax_xent import linear_cross_entropy

        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(N, C), dtype)
        w = jnp.asarray(rs.randn(V, C) * 0.02, dtype)
        y = jnp.asarray(rs.randint(0, V, (N,)))

        def step(x, w, y):
            # Both backward kernels, as a train step runs them: a dw
            # nothing reads is dropped from the program.
            gx, gw = jax.grad(lambda x, w: linear_cross_entropy(
                x, w, y, block_n=bn, block_v=bv).mean(), (0, 1))(x, w)
            return x + (1e-8 * (gx + gw[0])).astype(x.dtype)  # see flash

        dt, cal["chain"] = _timed_chain(step, (x, w, y),
                                        chain=cal["chain"])
        return dt

    return get_or_tune("linear_xent", sig, cands, bench, default)


def scan_blocks(B: int, T: int, Dn: int, N: int, default: Tuple[int, int],
                candidates: Sequence[Tuple[int, int]],
                pick_chunk) -> Tuple[int, int]:
    """Autotuned (chunk, block_d) for a selective scan over [B, T, Dn]
    with N states, among ``candidates`` deduplicated by the chunk they snap
    to on T (``pick_chunk``). Forward and backward are timed together: the
    backward holds a chunk's states in VMEM and is the one a large chunk
    overflows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sig = f"B{B}.T{T}.D{Dn}.N{N}"
    if 1.0 * B * T * Dn * N < 1e8:   # a toy scan: don't sweep
        return default
    seen, cands = set(), []
    for chunk, block_d in candidates:
        eff = (pick_chunk(T, chunk, N), block_d)
        if eff[0] is None or eff in seen:
            continue
        seen.add(eff)
        cands.append((chunk, block_d))
    if len(cands) <= 1:
        return default

    cal = {"chain": None}

    def bench(cand):
        chunk, block_d = cand
        from .selective_scan import selective_scan

        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(B, T, Dn), jnp.float32)
        dt = jnp.asarray(rs.uniform(1e-3, 1e-1, (B, T, Dn)), jnp.float32)
        A = -jnp.asarray(np.tile(np.arange(1.0, N + 1), (Dn, 1)),
                         jnp.float32)
        Bm = jnp.asarray(rs.randn(B, T, N), jnp.float32)
        Cm = jnp.asarray(rs.randn(B, T, N), jnp.float32)
        skip = jnp.ones((Dn,), jnp.float32)

        def step(x, dt, A, Bm, Cm, skip):
            g = jax.grad(lambda *ops: selective_scan(
                *ops, chunk=chunk, block_d=block_d).sum(),
                argnums=tuple(range(6)))(x, dt, A, Bm, Cm, skip)
            # Every gradient into the carry (see flash_blocks): one that
            # nothing reads would be dropped with the work that makes it.
            rest = g[1] + g[2].sum() + g[3].sum() + g[4].sum() + g[5]
            return x + 1e-8 * (g[0] + rest)

        dt_s, cal["chain"] = _timed_chain(step, (x, dt, A, Bm, Cm, skip),
                                          chain=cal["chain"])
        return dt_s

    return get_or_tune("selective_scan", sig, cands, bench, default)
