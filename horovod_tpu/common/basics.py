"""Core world state: init/shutdown, device mesh, rank/size queries.

Reference surface: ``HorovodBasics`` (horovod/common/basics.py:22-258) backed
by the C ABI ``horovod_init/rank/size/local_rank/...`` (operations.cc:685-889).

TPU-native redesign
-------------------
The reference runs **one process per GPU**; a rank is a process. On TPU the
idiomatic unit is **one process per host, one rank per chip**, with all chips
of a job joined in a single :class:`jax.sharding.Mesh` (single-controller
SPMD). We therefore keep Horovod's three-level world vocabulary but map it
onto the mesh:

====================  =============================================
Horovod concept        horovod_tpu mapping
====================  =============================================
rank                  global chip index (``hvd_cross * local_size + hvd_local``)
local_rank            chip index within this host (mesh axis ``hvd_local``)
cross_rank            host/process index (mesh axis ``hvd_cross``)
size                  total chips in the mesh
local_size            chips per host
cross_size            number of hosts
====================  =============================================

The mesh is always 2-D ``(hvd_cross, hvd_local)`` so hierarchical collectives
(intra-host over ICI, cross-host over DCN) fall out of the axis structure the
same way the reference splits ``local_comm``/``cross_comm``
(mpi_context.h:78-84, nccl_operations.cc:190-380).

``rank()``/``local_rank()``/``cross_rank()`` are **context sensitive**: inside
a ``jax.shard_map`` over the Horovod mesh they return the traced per-chip
index (so model code like ``if hvd.rank() == 0`` compiles to a per-device
predicate, matching the per-process value a reference user would see); in
eager host code they return the index of this process's *leader chip*.
"""

from __future__ import annotations

import atexit
import os
import threading
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from . import config as _config
from . import counters as _counters
from .exceptions import NotInitializedError

# Mesh axis names. The pair mirrors the reference's local/cross communicator
# split (mpi_context.h:78-84). ``HVD_AXES`` is the flat "world" axis tuple —
# psum over it is the reference's flat ring allreduce. ``POD_AXIS`` is the
# optional third hierarchy level (multi-pod topologies, ``mesh_shape=
# (cross, local, pods)`` with pods > 1): when present the mesh is 3-D
# ``(hvd_pod, hvd_cross, hvd_local)`` and ``ALL_AXES`` in that order is the
# full world tuple (rank-major lex order matches the mesh layout).
CROSS_AXIS = "hvd_cross"
LOCAL_AXIS = "hvd_local"
POD_AXIS = "hvd_pod"
HVD_AXES: Tuple[str, str] = (CROSS_AXIS, LOCAL_AXIS)
ALL_AXES: Tuple[str, str, str] = (POD_AXIS, CROSS_AXIS, LOCAL_AXIS)

# Pipeline-parallel mesh axis (docs/pipeline.md). Deliberately NOT part of
# ALL_AXES: the pp axis carries pipeline *stages*, not data replicas — a
# gradient collective over the "world" must never sum across ranks that
# hold different model layers, so every axes=None collective resolves to
# the data axes only and the pp axis is reached explicitly (the
# ``send``-leg ppermutes of parallel/pipeline.py).
PP_AXIS = "hvd_pp"

# Expert-parallel mesh axis (docs/moe.md). The same dedicated-axis
# pattern as PP_AXIS: the ep axis carries expert *groups*, not data
# replicas — expert parameters differ per ep rank, so a gradient
# collective over the "world" must never sum across expert groups. Every
# axes=None collective resolves to the data axes only; the ep axis is
# reached explicitly by the MoE dispatch/combine ``a2a`` wire-plan legs
# (horovod_tpu/moe/layer.py).
EP_AXIS = "hvd_ep"

# The single spelling every horovod_tpu caller (and the test suite, via
# ``hvd.shard_map``) goes through.
shard_map = jax.shard_map


class _State:
    """Process-global framework state (reference: HorovodGlobalState,
    global_state.h:42-122 — minus the background-thread machinery, which on
    TPU lives in the native controller, see horovod_tpu/cc/)."""

    def __init__(self) -> None:
        self.lock = threading.RLock()
        self.initialized = False
        self.config: Optional[_config.Config] = None
        self.mesh: Optional[Mesh] = None
        self.process_index: int = 0
        self.process_count: int = 1
        self.local_device_count: int = 0
        self.timeline = None  # utils.timeline.Timeline, attached lazily
        self.controller = None  # runtime controller client (eager path)
        self.joined = False


_state = _State()


def _build_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    mesh_shape: Optional[Tuple[int, ...]] = None,
    pp_stages: Optional[int] = None,
    ep_size: Optional[int] = None,
) -> Mesh:
    """Arrange all job devices into the 2-D (cross, local) Horovod mesh.

    Devices are ordered host-major so that chips on the same host are
    contiguous along ``hvd_local`` — the layout that keeps ``hvd_local``
    collectives on ICI and only ``hvd_cross`` traffic on DCN (the analogue of
    the reference packing ranks host-by-host, hosts.py:100-150).

    ``mesh_shape=(cross, local)`` overrides the inferred host/chip split —
    used to emulate a multi-host topology on a single host (tests, dryruns)
    or to re-slice a multi-slice pod. ``mesh_shape=(cross, local, pods)``
    with pods > 1 builds the 3-level ``(hvd_pod, hvd_cross, hvd_local)``
    mesh — the topology the wire-plan compiler's 3-level tree plans
    target (docs/wire-plan.md); pods == 1 collapses to the 2-D mesh.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if (ep_size is not None and ep_size > 1
            and pp_stages is not None and pp_stages > 1):
        # 4-D composed mesh (docs/parallelism.md): (hvd_pp, hvd_ep,
        # hvd_cross, hvd_local). The pp axis leads so consecutive
        # stages sit a full (ep x data)-mesh apart — the inter-stage
        # send still crosses the slowest link class present — and the
        # ep axis nests inside a stage so expert dispatch/combine
        # all-to-alls stay STAGE-LOCAL (an a2a must never mix tokens
        # that belong to different pipeline stages). Data shards and
        # gradient collectives stay on (cross, local) per (stage,
        # expert-group) cell.
        if mesh_shape is not None and len(mesh_shape) == 3:
            raise ValueError(
                "pp_stages x ep_size does not compose with a 3-level "
                "(cross, local, pods) mesh_shape — the pp/ep axes take "
                "the leading mesh dimensions the pod axis would use")
        if mesh_shape is not None:
            cross, local = mesh_shape
        else:
            if len(devices) % (pp_stages * ep_size):
                raise ValueError(
                    f"pp_stages {pp_stages} x ep_size {ep_size} does "
                    f"not divide {len(devices)} devices")
            cross, local = 1, len(devices) // (pp_stages * ep_size)
        if pp_stages * ep_size * cross * local != len(devices):
            raise ValueError(
                f"pp_stages {pp_stages} x ep_size {ep_size} x "
                f"mesh_shape ({cross}, {local}) does not cover "
                f"{len(devices)} devices")
        grid = np.array(devices, dtype=object).reshape(
            pp_stages, ep_size, cross, local)
        return Mesh(grid, (PP_AXIS, EP_AXIS, CROSS_AXIS, LOCAL_AXIS))
    if ep_size is not None and ep_size > 1:
        # Expert-parallel mesh (docs/moe.md): a leading hvd_ep axis of
        # expert groups over the (cross, local) data mesh — the same
        # leading-axis layout as the pipeline mesh, so consecutive ep
        # groups sit a full data-mesh apart and the dispatch/combine
        # all-to-all crosses the slowest link class present.
        if mesh_shape is not None and len(mesh_shape) == 3:
            raise ValueError(
                "ep_size does not compose with a 3-level "
                "(cross, local, pods) mesh_shape yet — the ep axis takes "
                "the leading mesh dimension the pod axis would use")
        if mesh_shape is not None:
            cross, local = mesh_shape
        else:
            if len(devices) % ep_size:
                raise ValueError(
                    f"ep_size {ep_size} does not divide "
                    f"{len(devices)} devices")
            cross, local = 1, len(devices) // ep_size
        if ep_size * cross * local != len(devices):
            raise ValueError(
                f"ep_size {ep_size} x mesh_shape ({cross}, {local}) "
                f"does not cover {len(devices)} devices")
        grid = np.array(devices, dtype=object).reshape(
            ep_size, cross, local)
        return Mesh(grid, (EP_AXIS, CROSS_AXIS, LOCAL_AXIS))
    if pp_stages is not None and pp_stages > 1:
        # Pipeline mesh: a leading hvd_pp axis of pipeline stages over
        # the (cross, local) data mesh. Consecutive stages sit a full
        # data-mesh apart in the device order, so the inter-stage hop
        # crosses the slowest link class present (docs/pipeline.md).
        if mesh_shape is not None and len(mesh_shape) == 3:
            raise ValueError(
                "pp_stages does not compose with a 3-level "
                "(cross, local, pods) mesh_shape yet — the pp axis takes "
                "the leading mesh dimension the pod axis would use")
        if mesh_shape is not None:
            cross, local = mesh_shape
        else:
            if len(devices) % pp_stages:
                raise ValueError(
                    f"pp_stages {pp_stages} does not divide "
                    f"{len(devices)} devices")
            cross, local = 1, len(devices) // pp_stages
        if pp_stages * cross * local != len(devices):
            raise ValueError(
                f"pp_stages {pp_stages} x mesh_shape ({cross}, {local}) "
                f"does not cover {len(devices)} devices")
        grid = np.array(devices, dtype=object).reshape(
            pp_stages, cross, local)
        return Mesh(grid, (PP_AXIS, CROSS_AXIS, LOCAL_AXIS))
    if mesh_shape is not None:
        if len(mesh_shape) == 3:
            cross, local, pods = mesh_shape
        elif len(mesh_shape) == 2:
            (cross, local), pods = mesh_shape, 1
        else:
            raise ValueError(
                f"mesh_shape must be (cross, local) or "
                f"(cross, local, pods), got {mesh_shape}")
        if cross * local * pods != len(devices):
            raise ValueError(
                f"mesh_shape {mesh_shape} does not cover {len(devices)} devices")
        if pods > 1:
            grid = np.array(devices, dtype=object).reshape(
                pods, cross, local)
            return Mesh(grid, ALL_AXES)
        grid = np.array(devices, dtype=object).reshape(cross, local)
        return Mesh(grid, HVD_AXES)
    n_proc = max(1, jax.process_count())
    per_proc = len(devices) // n_proc if n_proc > 1 else len(devices)
    if n_proc > 1 and per_proc * n_proc == len(devices):
        # Host-major ordering: sort by (process_index, id).
        devices.sort(key=lambda d: (d.process_index, d.id))
        grid = np.array(devices, dtype=object).reshape(n_proc, per_proc)
    else:
        grid = np.array(devices, dtype=object).reshape(1, len(devices))
    return Mesh(grid, HVD_AXES)


# Optional hook invoked (from a watcher thread) with the rank-0 controller's
# actually-bound port once its listener is up, while world formation is
# still in progress. Set by the elastic rendezvous before init() so the
# OS-assigned port (HOROVOD_CONTROLLER_PORT=0) can be reported to the
# elastic driver — port allocation happens on the rank-0 host, never as a
# driver-side free-port guess.
_controller_port_callback = [None]


def set_controller_port_callback(fn) -> None:
    _controller_port_callback[0] = fn


def _bridge_jsm_env() -> None:
    """Map jsrun's JSM_NAMESPACE_* identity vars onto the HOROVOD_* env
    contract when the latter is absent (jsrun launch path,
    runner/js_run.py: jsrun is the process placer; rank identity comes
    from the job-step manager, reference js_run.py + launch.py:463)."""
    bridge = {
        "HOROVOD_RANK": "JSM_NAMESPACE_RANK",
        "HOROVOD_SIZE": "JSM_NAMESPACE_SIZE",
        "HOROVOD_LOCAL_RANK": "JSM_NAMESPACE_LOCAL_RANK",
        "HOROVOD_LOCAL_SIZE": "JSM_NAMESPACE_LOCAL_SIZE",
    }
    for hvd_key, jsm_key in bridge.items():
        if hvd_key not in os.environ and jsm_key in os.environ:
            os.environ[hvd_key] = os.environ[jsm_key]


def _bridge_mpi_env() -> None:
    """Map mpirun's rank-identity vars onto the HOROVOD_* env contract
    when the latter is absent (mpirun launch path, runner/mpi_run.py:
    mpirun is the process placer; OpenMPI/Spectrum export
    ``OMPI_COMM_WORLD_*``, MPICH/Hydra export ``PMI_*``)."""
    bridges = (
        {  # OpenMPI / IBM Spectrum MPI
            "HOROVOD_RANK": "OMPI_COMM_WORLD_RANK",
            "HOROVOD_SIZE": "OMPI_COMM_WORLD_SIZE",
            "HOROVOD_LOCAL_RANK": "OMPI_COMM_WORLD_LOCAL_RANK",
            "HOROVOD_LOCAL_SIZE": "OMPI_COMM_WORLD_LOCAL_SIZE",
        },
        {  # MPICH (Hydra PMI; local identity rides MPI_LOCALRANKID)
            "HOROVOD_RANK": "PMI_RANK",
            "HOROVOD_SIZE": "PMI_SIZE",
            "HOROVOD_LOCAL_RANK": "?MPI_LOCALRANKID",
            "HOROVOD_LOCAL_SIZE": "?MPI_LOCALNRANKS",
        },
    )
    for bridge in bridges:
        # "?"-prefixed sources are optional; the rest gate the bridge.
        required = {k: v for k, v in bridge.items()
                    if not v.startswith("?")}
        if all(v in os.environ for v in required.values()):
            for hvd_key, mpi_key in bridge.items():
                mpi_key = mpi_key.lstrip("?")
                if mpi_key in os.environ:
                    os.environ.setdefault(hvd_key, os.environ[mpi_key])
            return


def init(
    comm=None,
    devices: Optional[Sequence[jax.Device]] = None,
    mesh_shape: Optional[Tuple[int, int]] = None,
    pp_stages: Optional[int] = None,
    ep_size: Optional[int] = None,
) -> None:
    """Initialize the framework (reference: hvd.init(), basics.py:33 →
    InitializeHorovodOnce, operations.cc:628-674).

    Unlike the reference there is no background communication thread to spawn
    for the compiled path: collectives are compiled *into* the XLA program
    over the ICI mesh. What init does:

    1. read env knobs into an immutable :class:`Config`;
    2. build the global 2-D device mesh;
    3. (multi-host) assume ``jax.distributed.initialize`` was already called
       by the launcher (runner/), mirroring the launcher-injected
       ``HOROVOD_RANK/SIZE`` env contract (gloo_run.py:65-76);
    4. start the timeline if ``HOROVOD_TIMELINE`` is set.

    ``comm`` is accepted for API parity with the reference (an MPI
    communicator there) and must be ``None`` or a device list.
    """
    with _state.lock:
        if _state.initialized:
            return
        if comm is not None and devices is None:
            devices = comm  # parity: allow init(devices)
        _bridge_jsm_env()
        _bridge_mpi_env()
        _state.config = _config.from_env()
        if _state.config.overlap:
            # Before the mesh (= before PJRT client creation): the async-
            # collective/LHS flags only apply to a fresh backend. Graceful
            # no-op off-TPU (docs/overlap.md).
            from .backend import enable_overlap_scheduling

            enable_overlap_scheduling()
        # Compile-once runtime (docs/compile.md): arm JAX's persistent
        # compilation cache BEFORE the mesh exists — the knob only
        # covers compiles issued after arming, and the first collective
        # compile can happen as soon as the mesh does.
        from ..compile import cache as _compile_cache

        _compile_cache.arm_persistent_cache(_state.config)
        if pp_stages is None:
            pp_stages = _state.config.pp_stages or None
        if ep_size is None:
            ep_size = _state.config.ep_size or None
        _state.mesh = _build_mesh(devices, mesh_shape, pp_stages, ep_size)
        _state.process_index = jax.process_index()
        _state.process_count = jax.process_count()
        _state.local_device_count = int(_state.mesh.devices.shape[-1])
        # Launcher-injected env contract (HOROVOD_RANK/SIZE +
        # HOROVOD_CONTROLLER_ADDR, gloo_run.py:65-76): start the native
        # control-plane core. It owns the rank-0 coordinator loop and the
        # TCP data plane for eager (host) collectives between worker
        # processes — the role MPI/Gloo play in the reference.
        cfg = _state.config
        if (cfg.size is not None and cfg.size > 1
                and cfg.controller != "none"):
            from .. import cc

            port_cb = _controller_port_callback[0]
            # Env check BEFORE importing runner/: non-bootstrap inits
            # (elastic, jax.distributed) must not pay the launcher-package
            # import on this path.
            if os.environ.get("HOROVOD_CONTROLLER_BOOTSTRAP") == "kv":
                # Static-launch KV protocol (runner/bootstrap.py): rank 0
                # binds port 0 and publishes; other ranks resolve the
                # controller address from the KV before native init.
                from ..runner import bootstrap

                rank = int(os.environ.get("HOROVOD_RANK", "0"))
                cb = bootstrap.apply(rank)
                if cb is not None:
                    port_cb = cb
            _state.controller = cc.CoreContext(
                bound_port_callback=port_cb)
            if _state.process_count == 1:
                # Process-world mode (no jax.distributed): each worker
                # process is one Horovod rank, exactly the reference's
                # process model. The local mesh serves in-process
                # compiled collectives only.
                _state.process_index = _state.controller.rank()
                _state.process_count = _state.controller.size()
        if _state.config.timeline:
            from ..utils.timeline import Timeline

            _state.timeline = Timeline(_state.config.timeline,
                                       mark_cycles=_state.config.timeline_mark_cycles)
        _state.initialized = True
        # Observability layer: metric sinks (JSONL / Prometheus / timeline
        # mirrors) and the live StallInspector watchdog. The registry
        # itself is process-global and survives shutdown→init cycles
        # (docs/observability.md).
        from .. import monitor

        monitor.start_from_env(_state.config)
    # Outside the lock (uses eager collectives): multi-host runs verify
    # that every host loaded an identical kernel-autotune cache before
    # any cached block choice may shape a compiled program.
    if _state.process_count > 1:
        from ..ops import kernel_autotune

        kernel_autotune.verify_multihost_cache()


# One warning per process: HOROVOD_AUTOTUNE=1 that never reached a
# tuning session is a silent no-op on the compiled path (bucket plans are
# trace-time; the knob activates hvd.autotune_session, docs/autotune.md).
_autotune_unused_warned = [False]


def _warn_autotune_unused(cfg: Optional[_config.Config]) -> None:
    if cfg is None or not cfg.autotune or _autotune_unused_warned[0]:
        return
    from ..autotune import driver as _autotune_driver

    if _autotune_driver.sessions_run() > 0:
        return
    _autotune_unused_warned[0] = True
    import logging

    logging.getLogger("horovod_tpu.autotune").warning(
        "HOROVOD_AUTOTUNE=1 but no tuning session ran: on the compiled "
        "(XLA) path the collective tunables are fixed at trace time, so "
        "autotuning requires an explicit session — wrap your step in "
        "hvd.autotune_session(make_step, cache_key=params) and build the "
        "step with the returned TunedParams (tuned_params= on "
        "DistributedOptimizer / allreduce_pytree). Without it the knob "
        "changes nothing. See docs/autotune.md.")


def shutdown() -> None:
    """Tear down framework state (reference: horovod_shutdown,
    operations.cc:676-683). Safe to call multiple times; init() can be called
    again afterwards (the elastic reset path relies on this,
    common/elastic.py:147-168)."""
    _warn_autotune_unused(_state.config)
    if _state.initialized:
        # Before the timeline closes: final metric flush (the timeline
        # mirror rides it), stop the stall watchdog / reporter / endpoint.
        # Registry values persist into the next incarnation.
        from .. import monitor

        monitor.on_shutdown()
    with _state.lock:
        if _state.timeline is not None:
            _state.timeline.close()
            _state.timeline = None
        if _state.controller is not None:
            _state.controller.close()
            _state.controller = None
        _state.initialized = False
        _state.mesh = None
        _state.config = None
        _state.joined = False
        # Re-align auto-generated collective names for the elastic
        # shutdown→init cycle (survivors and respawned workers must both
        # count from 0).
        from ..ops import collective_ops

        collective_ops._reset_eager_state()
        # New incarnation, fresh fault/retry counters (totals persist).
        _counters.reset_incarnation()


atexit.register(shutdown)


def fault_counters(total: bool = False) -> dict:
    """Snapshot of the fault/retry counters (RPC retries, injected chaos
    faults, blacklist transitions, stall-watchdog firings). Scope is the
    current world incarnation by default — counters clear on
    ``shutdown()``, so an elastic job reads per-incarnation numbers;
    ``total=True`` returns process-lifetime cumulative values. Does not
    require ``init()``: the runner/driver processes record too."""
    return _counters.counters(total=total)


def is_initialized() -> bool:
    """Reference: horovod_is_initialized (operations.cc:759)."""
    return _state.initialized


def _require_init() -> _State:
    if not _state.initialized:
        raise NotInitializedError()
    return _state


def mesh() -> Mesh:
    """The global 2-D ``(hvd_cross, hvd_local)`` device mesh."""
    return _require_init().mesh


def config() -> _config.Config:
    return _require_init().config


def timeline():
    return _require_init().timeline


def _bound_axes() -> frozenset:
    """Names of mesh axes bound in the current trace (inside shard_map)."""
    try:
        from jax._src.core import get_axis_env

        return frozenset(get_axis_env().axis_sizes)
    except Exception:  # pragma: no cover - private-API drift fallback
        bound = set()
        for name in ALL_AXES:
            try:
                jax.lax.axis_index(name)
                bound.add(name)
            except NameError:
                pass
        return frozenset(bound)


def _axis_size(name) -> int:
    """Size of a bound mesh axis."""
    try:
        return jax.lax.axis_size(name)
    except NameError:
        raise _unbound_axis_error(name) from None


def _unbound_axis_error(name) -> Exception:
    """A collective asked for a mesh axis that is not bound in the current
    trace. Uninitialized backend → the reference-style "call hvd.init()
    first" error instead of the raw KeyError/NameError; initialized →
    explain the shard_map requirement."""
    if not is_initialized():
        return NotInitializedError(
            f"Horovod-TPU (required by a collective over mesh axis "
            f"{name!r})")
    return ValueError(
        f"mesh axis {name!r} is not bound in the current trace: compiled "
        f"collectives must run inside hvd.shard_map over the Horovod "
        f"mesh (hvd.mesh()); omit axes= in eager host code to use the "
        f"process-world path")


def _trace_world_axes() -> Tuple[str, ...]:
    """Horovod mesh axes bound in the current trace, in rank-major
    ``(pod, cross, local)`` order — the 3-level-aware source for
    per-trace rank computation and axis resolution."""
    bound = _bound_axes()
    return tuple(a for a in ALL_AXES if a in bound)


def world_axes() -> Tuple[str, ...]:
    """Axis tuple of the full world mesh: ``(hvd_pod, hvd_cross,
    hvd_local)`` on a 3-level mesh, ``HVD_AXES`` otherwise (including
    before init — the 2-level names are the back-compat default)."""
    s = _state
    if (s.initialized and s.mesh is not None
            and s.mesh.devices.ndim == 3
            and s.mesh.axis_names[0] == POD_AXIS):
        return ALL_AXES
    # A pipeline mesh's hvd_pp axis (and an expert-parallel mesh's
    # hvd_ep axis) is NOT a world/data axis: data shards and gradient
    # collectives stay on (cross, local) per stage / per expert group.
    return HVD_AXES


def in_hvd_context() -> bool:
    """True when tracing under shard_map over the Horovod mesh axes."""
    bound = _bound_axes()
    return (CROSS_AXIS in bound or LOCAL_AXIS in bound
            or POD_AXIS in bound)


def _process_world() -> bool:
    """True in process-world mode: the native controller defines the world
    (one rank per worker process, the reference's process model) because
    jax.distributed is not gluing the devices into one global mesh."""
    s = _state
    return s.controller is not None and jax.process_count() == 1


def size() -> int:
    """Total number of ranks. Mesh chips under single-controller SPMD;
    worker processes in process-world mode. Reference: horovod_size
    (operations.cc:795)."""
    s = _require_init()
    if _process_world():
        return s.controller.size()
    return int(s.mesh.devices.size)


def local_size() -> int:
    """Ranks on this host. Reference: horovod_local_size (operations.cc:787)."""
    s = _require_init()
    if _process_world():
        return s.controller.local_size()
    return s.local_device_count


def cross_size() -> int:
    """Number of hosts. Reference: horovod_cross_size (operations.cc:817)."""
    s = _require_init()
    if _process_world():
        return s.controller.cross_size()
    return int(s.mesh.devices.shape[-2])


def pod_size() -> int:
    """Number of pods (the third hierarchy level): the leading mesh dim
    of a 3-level ``(pod, cross, local)`` mesh, else 1."""
    s = _require_init()
    if (s.mesh is not None and s.mesh.devices.ndim == 3
            and s.mesh.axis_names[0] == POD_AXIS):
        return int(s.mesh.devices.shape[0])
    return 1


def pp_size() -> int:
    """Number of pipeline stages: the leading ``hvd_pp`` mesh dim of a
    pipeline mesh (``init(pp_stages=...)`` / ``HOROVOD_PP_STAGES``),
    else 1 (docs/pipeline.md). On the 4-D composed ``(pp, ep, cross,
    local)`` mesh the pp axis still leads."""
    s = _require_init()
    if (s.mesh is not None and s.mesh.devices.ndim in (3, 4)
            and s.mesh.axis_names[0] == PP_AXIS):
        return int(s.mesh.devices.shape[0])
    return 1


def ep_size() -> int:
    """Number of expert-parallel groups: the leading ``hvd_ep`` mesh dim
    of an expert-parallel mesh (``init(ep_size=...)`` /
    ``HOROVOD_EP_SIZE``), else 1 (docs/moe.md). On the 4-D composed
    ``(pp, ep, cross, local)`` mesh the ep axis sits second, inside a
    stage."""
    s = _require_init()
    if (s.mesh is not None and s.mesh.devices.ndim == 3
            and s.mesh.axis_names[0] == EP_AXIS):
        return int(s.mesh.devices.shape[0])
    if (s.mesh is not None and s.mesh.devices.ndim == 4
            and s.mesh.axis_names[1] == EP_AXIS):
        return int(s.mesh.devices.shape[1])
    return 1


def data_mesh_shape() -> Tuple[int, ...]:
    """The DATA mesh shape ``(cross, local[, pods])`` — the shape every
    plan derivation prices. On a pipeline or expert-parallel mesh the
    leading ``hvd_pp``/``hvd_ep`` dim is excluded: gradient collectives
    run per-stage / per-expert-group over the data axes only."""
    s = _require_init()
    shp = s.mesh.devices.shape
    if len(shp) == 2:
        return (int(shp[0]), int(shp[1]))
    if len(shp) == 4:
        # 4-D composed (pp, ep, cross, local) mesh: the data mesh is
        # the trailing pair — one (stage, expert-group) cell.
        return (int(shp[2]), int(shp[3]))
    if s.mesh.axis_names[0] in (PP_AXIS, EP_AXIS):
        return (int(shp[1]), int(shp[2]))
    return (int(shp[1]), int(shp[2]), int(shp[0]))


def mesh_geometry(mesh_shape=None, mesh=None) -> str:
    """Geometry fingerprint ``mesh<CxL[xP]>|world<N>|<device-kind>``.

    Keys every geometry-bound persisted artifact — the autotune
    warm-start cache entries and the link-calibration store
    (docs/cost-model.md): a tuned winner or a calibrated (bandwidth,
    latency, quant-rate) triple only transfers to an identical topology
    on the same chip kind. ``mesh_shape`` is ``(cross, local[, pods])``;
    with neither argument the live mesh is used (``nomesh`` before
    init)."""
    if mesh is None and mesh_shape is None and is_initialized():
        mesh = _state.mesh
    pp = ""
    if mesh is not None and mesh_shape is None:
        shp = mesh.devices.shape
        if len(shp) == 2:
            mesh_shape = tuple(int(v) for v in shp)
        elif len(shp) == 4:
            # 4-D composed mesh: the fingerprint is the per-cell DATA
            # mesh plus the combined pp/ep marker — a winner tuned at
            # one (stage, expert-group) geometry never warm-starts
            # another (docs/parallelism.md).
            mesh_shape = (int(shp[2]), int(shp[3]))
            pp = f"pp{int(shp[0])}.ep{int(shp[1])}"
        elif mesh.axis_names[0] == PP_AXIS:
            # Pipeline mesh: the fingerprint is the DATA mesh plus an
            # explicit pp marker — a winner tuned at one stage count
            # never warm-starts another (docs/pipeline.md).
            mesh_shape = (int(shp[1]), int(shp[2]))
            pp = f"pp{int(shp[0])}"
        elif mesh.axis_names[0] == EP_AXIS:
            # Expert-parallel mesh: same discipline — a winner tuned at
            # one expert-group count never warm-starts another
            # (docs/moe.md).
            mesh_shape = (int(shp[1]), int(shp[2]))
            pp = f"ep{int(shp[0])}"
        else:
            mesh_shape = (int(shp[1]), int(shp[2]), int(shp[0]))
    if mesh_shape:
        shape = "x".join(str(int(v)) for v in mesh_shape) + pp
        world = 1
        for v in mesh_shape:
            world *= int(v)
    else:
        shape = "nomesh"
        world = size() if is_initialized() else 1
    try:
        devs = (list(mesh.devices.ravel()) if mesh is not None
                else jax.devices())
        kind = getattr(devs[0], "device_kind", "unknown") if devs \
            else "unknown"
    except Exception:  # pragma: no cover - backendless processes
        kind = "unknown"
    kind = str(kind or "unknown").strip().lower().replace(" ", "-")
    return f"mesh{shape}|world{world}|{kind}"


def rank():
    """Global rank. Traced per-chip inside shard_map; process rank in eager
    code. Reference: horovod_rank (operations.cc:771)."""
    s = _require_init()
    if in_hvd_context():
        return jax.lax.axis_index(_trace_world_axes() or HVD_AXES)
    if _process_world():
        return s.controller.rank()
    return s.process_index * s.local_device_count


def local_rank():
    """Rank within the host. Reference: horovod_local_rank
    (operations.cc:779)."""
    s = _require_init()
    if in_hvd_context():
        return jax.lax.axis_index(LOCAL_AXIS)
    if _process_world():
        return s.controller.local_rank()
    return 0


def cross_rank():
    """Host index. Reference: horovod_cross_rank (operations.cc:809)."""
    s = _require_init()
    if in_hvd_context():
        return jax.lax.axis_index(CROSS_AXIS)
    if _process_world():
        return s.controller.cross_rank()
    return s.process_index


def is_homogeneous() -> bool:
    """True when every host has the same number of chips (always true for a
    well-formed mesh). Reference: horovod_is_homogeneous (operations.cc:825)."""
    _require_init()
    return True


def mpi_threads_supported() -> bool:
    """Parity stub (reference: horovod_mpi_threads_supported,
    operations.cc:833). The compiled-collective path has no MPI; the eager
    control plane is thread-safe, so report True."""
    _require_init()
    return True


# --- convenience sharding helpers -----------------------------------------


def data_sharding(extra: Sequence[Optional[str]] = ()) -> NamedSharding:
    """NamedSharding that splits the leading (batch) dim over all ranks."""
    return NamedSharding(mesh(), PartitionSpec(world_axes(), *extra))


def replicated_sharding() -> NamedSharding:
    """NamedSharding that replicates a value on every rank."""
    return NamedSharding(mesh(), PartitionSpec())


def local_batch_size(global_batch: int) -> int:
    n = size()
    if global_batch % n != 0:
        raise ValueError(
            f"global batch {global_batch} not divisible by world size {n}"
        )
    return global_batch // n
