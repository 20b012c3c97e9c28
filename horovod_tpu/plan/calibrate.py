"""Link-class calibration: measure (bandwidth, latency, quant-rate) per
level with a microbenchmark sweep and persist it beside the autotune
cache.

The cost model (:mod:`~horovod_tpu.plan.cost`) prices plans from
per-link ``(bandwidth_gbps, latency_us, quant_rate_gbps)`` triples. The
static defaults are honest nominal numbers, but HiCCL's premise is that
the triples should be *measured*: :func:`calibrate_links` times a
per-level ``lax.ppermute`` at 3–4 payload sizes (one directed ring hop =
one link traversal, the cleanest alpha-beta probe a compiled mesh
offers), fits ``t(n) = alpha + n/beta`` by least squares, and times the
blockwise int8 quantize + dequant-accumulate kernel pair the same way
for the quant rate.

Persistence contract (the part training depends on):

* the calibration lives in ONE JSON file next to the autotune cache
  (``HOROVOD_CALIBRATION_CACHE``, default ``link_calibration.json``
  beside ``HOROVOD_AUTOTUNE_CACHE``), keyed by the mesh **geometry
  fingerprint** (shape × world × device kind,
  :func:`horovod_tpu.common.basics.mesh_geometry`) — a sweep from a
  different topology or chip is never trusted;
* a geometry-key miss means re-sweep (or static defaults), never a
  silently wrong model;
* a corrupted, unreadable, or missing file falls back to the static
  ``HOROVOD_BENCH_*`` defaults with a logged warning — calibration is an
  optimization and must NEVER abort training.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

from ..common import basics
from .cost import CostModel, LinkClass

log = logging.getLogger("horovod_tpu.plan")

# Bump when the sweep methodology changes enough to invalidate stored
# fits (sizes, fit form, kernel pair).
CALIBRATION_VERSION = 1

# Default sweep payloads, fp32 elements per device: 16 KiB – 4 MiB of
# wire per hop — small enough that a CPU-mesh sweep finishes in seconds,
# wide enough (256x) that the least-squares slope is bandwidth, not
# launch jitter.
DEFAULT_SWEEP_ELEMS = (4096, 32768, 262144, 1048576)


def calibration_path() -> str:
    """The calibration store: ``HOROVOD_CALIBRATION_CACHE``, defaulting
    to ``link_calibration.json`` beside the shared autotune cache."""
    explicit = os.environ.get("HOROVOD_CALIBRATION_CACHE")
    if explicit:
        return explicit
    from ..ops import kernel_autotune

    return os.path.join(os.path.dirname(kernel_autotune._cache_path()),
                        "link_calibration.json")


def geometry_key(mesh_shape=None) -> str:
    """Store key for one mesh geometry:
    ``linkcal|<mesh_geometry>|v<CALIBRATION_VERSION>``."""
    return (f"linkcal|{basics.mesh_geometry(mesh_shape=mesh_shape)}"
            f"|v{CALIBRATION_VERSION}")


@dataclasses.dataclass(frozen=True)
class Calibration:
    """One stored sweep: the fitted per-link triples plus the raw
    ``(bytes, seconds)`` points they were fitted from (kept for
    drift forensics — scripts/obs_report.py can re-fit)."""

    geometry: str
    links: Dict[str, LinkClass]
    points: Dict[str, List[Tuple[float, float]]]
    created_unix: float
    version: int = CALIBRATION_VERSION

    def to_dict(self) -> dict:
        return {
            "version": self.version,
            "geometry": self.geometry,
            "links": {k: v.as_dict() for k, v in self.links.items()},
            "points": {k: [[float(b), float(s)] for b, s in pts]
                       for k, pts in self.points.items()},
            "created_unix": self.created_unix,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "Calibration":
        return cls(
            geometry=str(d["geometry"]),
            links={k: LinkClass.from_dict(v)
                   for k, v in d["links"].items()},
            points={k: [(float(b), float(s)) for b, s in pts]
                    for k, pts in d.get("points", {}).items()},
            created_unix=float(d.get("created_unix", 0.0)),
            version=int(d.get("version", 1)),
        )

    def cost_model(self) -> CostModel:
        """The calibrated :class:`~horovod_tpu.plan.cost.CostModel`;
        link classes the sweep could not measure (absent mesh levels)
        keep the static defaults."""
        static = CostModel.from_env()
        return CostModel(
            ici=self.links.get("ici", static.ici),
            dcn=self.links.get("dcn", static.dcn),
            pod=self.links.get("pod", static.pod),
            source="calibrated",
            geometry=self.geometry,
        )


def alpha_beta_fit(points: Sequence[Tuple[float, float]],
                   *, fallback_gbps: float,
                   fallback_lat_us: float) -> Tuple[float, float]:
    """Least-squares ``t = alpha + bytes/beta`` over ``(bytes, secs)``
    points; returns ``(bandwidth_gbps, latency_us)``. A non-positive or
    degenerate slope (timer noise at CPU speeds) falls back to the
    static values — a calibration must never produce a nonsensical
    model."""
    pts = [(float(b), float(s)) for b, s in points]
    n = len(pts)
    if n < 2:
        return fallback_gbps, fallback_lat_us
    sx = sum(b for b, _ in pts)
    sy = sum(s for _, s in pts)
    sxx = sum(b * b for b, _ in pts)
    sxy = sum(b * s for b, s in pts)
    denom = n * sxx - sx * sx
    if denom <= 0:
        return fallback_gbps, fallback_lat_us
    slope = (n * sxy - sx * sy) / denom       # seconds per byte
    intercept = (sy - slope * sx) / n          # seconds
    if slope <= 0 or not (slope < float("inf")):
        return fallback_gbps, fallback_lat_us
    bandwidth_gbps = 1.0 / (slope * 1e9)
    latency_us = max(0.0, intercept * 1e6)
    return bandwidth_gbps, latency_us


def _time_call(fn, *args, reps: int = 3) -> float:
    """Min-of-reps wall time of a blocking jitted call (first call
    compiles and is discarded)."""
    import jax

    jax.block_until_ready(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _sweep_level(axis: str, sizes: Sequence[int],
                 reps: int) -> List[Tuple[float, float]]:
    """(bytes, seconds) of one directed ``lax.ppermute`` ring hop over
    ``axis`` at each payload size — n fp32 elements per device travel
    exactly one link of that class."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    mesh = basics.mesh()
    k = mesh.shape[axis]
    perm = [(i, (i + 1) % k) for i in range(k)]
    world_axes = basics.world_axes()
    pts: List[Tuple[float, float]] = []
    for n in sizes:
        x = jnp.arange(basics.size() * int(n), dtype=jnp.float32)

        def hop(xs):
            return lax.ppermute(xs, axis, perm)

        fn = jax.jit(basics.shard_map(
            hop, mesh=mesh, in_specs=P(world_axes),
            out_specs=P(world_axes)))
        pts.append((float(n) * 4.0, _time_call(fn, x, reps=reps)))
    return pts


def _sweep_quant(sizes: Sequence[int],
                 reps: int) -> List[Tuple[float, float]]:
    """(fp bytes, seconds) of the blockwise int8 quantize +
    dequant-accumulate pair (the rate the cost model charges)."""
    import jax
    import jax.numpy as jnp

    from .compiler import _dequant_accumulate, _quantize_blocks

    blk = 256
    pts: List[Tuple[float, float]] = []
    for n in sizes:
        nb = max(1, int(n) // blk)
        x = jnp.arange(nb * blk, dtype=jnp.float32).reshape(1, nb, blk)

        def pair(blocks):
            q, scales, _ = _quantize_blocks(blocks)
            return _dequant_accumulate(q, scales)

        fn = jax.jit(pair)
        pts.append((float(nb * blk) * 4.0, _time_call(fn, x, reps=reps)))
    return pts


def calibrate_links(*, sizes: Sequence[int] = DEFAULT_SWEEP_ELEMS,
                    reps: int = 3, store: bool = True) -> Calibration:
    """Run the microbenchmark sweep on the LIVE mesh (``hvd.init`` must
    have run) and return (and by default persist) the fitted
    :class:`Calibration`.

    Levels the mesh does not have (no cross hosts, no pods) are skipped
    — their link classes keep the static defaults, which is correct:
    they carry no traffic on this geometry."""
    if not basics.is_initialized():
        raise RuntimeError(
            "calibrate_links() needs an initialized mesh — call "
            "horovod_tpu.init() first")
    static = CostModel.from_env()
    geometry = basics.mesh_geometry()
    levels = {"ici": basics.LOCAL_AXIS, "dcn": basics.CROSS_AXIS}
    if basics.pod_size() > 1:
        levels["pod"] = basics.POD_AXIS
    mesh = basics.mesh()
    points: Dict[str, List[Tuple[float, float]]] = {}
    links: Dict[str, LinkClass] = {}
    t0 = time.perf_counter()
    for hop, axis in levels.items():
        if mesh.shape[axis] < 2:
            continue  # a size-1 level has no link to measure
        pts = _sweep_level(axis, sizes, reps)
        fb = static.link(hop)
        bw, lat = alpha_beta_fit(pts, fallback_gbps=fb.bandwidth_gbps,
                                 fallback_lat_us=fb.latency_us)
        points[hop] = pts
        links[hop] = LinkClass(bw, lat, fb.quant_rate_gbps)
    qpts = _sweep_quant(sizes, reps)
    qrate, _ = alpha_beta_fit(
        qpts, fallback_gbps=static.dcn.quant_rate_gbps,
        fallback_lat_us=0.0)
    points["quant"] = qpts
    links = {hop: dataclasses.replace(lk, quant_rate_gbps=qrate)
             for hop, lk in links.items()}
    calib = Calibration(geometry=geometry, links=links, points=points,
                        created_unix=time.time())
    log.warning(
        "horovod_tpu calibrate: %s swept %d link class(es) x %d sizes "
        "in %.1fs -> %s", geometry, len(links), len(sizes),
        time.perf_counter() - t0,
        {h: f"{lk.bandwidth_gbps:.2f}GB/s@{lk.latency_us:.1f}us"
         for h, lk in links.items()})
    if store:
        store_calibration(calib)
    return calib


# ---------------------------------------------------------------------------
# Persistence — same read-merge-write + atomic-replace discipline as the
# autotune cache it lives beside (ops/kernel_autotune.py).
# ---------------------------------------------------------------------------


def store_calibration(calib: Calibration) -> None:
    path = calibration_path()
    key = f"linkcal|{calib.geometry}|v{calib.version}"
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        import fcntl

        with open(path + ".lock", "w") as lockf:
            fcntl.flock(lockf, fcntl.LOCK_EX)
            disk: dict = {}
            try:
                with open(path) as f:
                    disk = json.load(f)
            except (FileNotFoundError, json.JSONDecodeError, ValueError):
                pass
            if not isinstance(disk, dict):
                disk = {}
            disk[key] = calib.to_dict()
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(disk, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        log.info("calibration stored under %s in %s", key, path)
    except OSError as e:  # persistence is an optimization, never fatal
        log.warning("calibration write to %s failed (%s); the sweep "
                    "stays in-process only", path, e)


def load_calibration(mesh_shape=None) -> Optional[Calibration]:
    """The stored calibration for this geometry, or None when the file
    is missing/corrupted (logged warning) or holds no entry for this
    geometry key (a mismatched mesh/world/chip forces a re-sweep)."""
    path = calibration_path()
    key = geometry_key(mesh_shape)
    try:
        with open(path) as f:
            disk = json.load(f)
    except FileNotFoundError:
        return None
    except (OSError, json.JSONDecodeError, ValueError) as e:
        log.warning(
            "horovod_tpu calibrate: calibration file %s unreadable "
            "(%s: %s) — falling back to the static HOROVOD_BENCH_* "
            "link model", path, type(e).__name__, e)
        return None
    entry = disk.get(key) if isinstance(disk, dict) else None
    if entry is None:
        log.info("calibration %s has no entry for %s (geometry changed "
                 "or never swept) — re-sweep or static defaults apply",
                 path, key)
        return None
    try:
        calib = Calibration.from_dict(entry)
    except (KeyError, TypeError, ValueError) as e:
        log.warning(
            "horovod_tpu calibrate: calibration entry %s in %s is "
            "malformed (%s: %s) — falling back to the static "
            "HOROVOD_BENCH_* link model", key, path,
            type(e).__name__, e)
        return None
    return calib


def get_cost_model(mesh_shape=None, *,
                   calibrate_missing: bool = False) -> CostModel:
    """The best available cost model for this geometry: calibrated when
    a matching sweep is stored, optionally sweeping on a miss
    (``calibrate_missing``, needs a live mesh), else the static env
    defaults. Never raises."""
    try:
        calib = load_calibration(mesh_shape)
        if calib is not None:
            return calib.cost_model()
        if calibrate_missing and basics.is_initialized() \
                and mesh_shape is None:
            return calibrate_links().cost_model()
    except Exception as e:  # never let pricing break training
        log.warning(
            "horovod_tpu calibrate: cost-model resolution failed "
            "(%s: %s) — using the static HOROVOD_BENCH_* link model",
            type(e).__name__, e)
    return CostModel.from_env()
