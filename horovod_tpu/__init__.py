"""horovod_tpu: a TPU-native distributed training framework.

A from-scratch rebuild of the capabilities of Horovod (reference:
gangiswag/horovod v0.20.3) designed for TPU hardware: collectives compile
into XLA programs over the ICI mesh via ``jax.shard_map``/``pjit`` instead of
running through a background NCCL/MPI thread; the host-side control plane
(launcher, rendezvous, elastic driver, eager collectives) mirrors the
reference's coordinator architecture.

Quick start (the reference's README recipe, TPU-style)::

    import horovod_tpu as hvd

    hvd.init()
    mesh = hvd.mesh()

    tx = hvd.DistributedOptimizer(optax.sgd(0.01 * hvd.size()))

    @jax.jit
    def train_step(params, opt_state, batch):
        def spmd(params, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            # grads are allreduced inside the optimizer update:
            updates, new_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_state, loss
        return jax.shard_map(spmd, mesh=mesh,
                             in_specs=(P(), hvd.data_pspec()),
                             out_specs=(P(), P(), P()))(params, batch)

API surface parity map (reference file → here):
  basics.py hvd.init/rank/size/...    → common/basics.py
  mpi_ops allreduce/allgather/...     → ops/collective_ops.py
  compression.py                      → ops/compression.py
  adasum (common/ops/adasum)          → ops/adasum.py
  tensor fusion (fusion_buffer)       → ops/fusion.py
  DistributedOptimizer                → parallel/optimizer.py
  DistributedGradientTape             → parallel/tape.py
  broadcast_variables/object          → parallel/functions.py
  SyncBatchNorm                       → parallel/sync_batch_norm.py
  elastic State/run                   → elastic/
  horovodrun launcher                 → runner/
  horovod.torch                       → torch/ (mpi_ops, optimizer, ...)
  horovod.tensorflow                  → tensorflow/ (ops, tape, optimizer)
  horovod.keras / tensorflow.keras    → keras/, _keras/, tensorflow/keras/
  horovod.mxnet                       → mxnet/ (gated: MXNet is EOL)
  parameter_manager + optim/ (GP/BO)  → autotune/ (hvd.autotune_session)
  (no reference analogue)             → parallel/sequence.py (ring/Ulysses
                                        attention), ops/flash_attention.py
                                        (Pallas flash kernel), models/gpt.py
"""

from .common.basics import (  # noqa: F401
    CROSS_AXIS,
    EP_AXIS,
    HVD_AXES,
    LOCAL_AXIS,
    POD_AXIS,
    PP_AXIS,
    cross_rank,
    cross_size,
    data_mesh_shape,
    data_sharding,
    ep_size,
    in_hvd_context,
    init,
    is_homogeneous,
    is_initialized,
    local_batch_size,
    local_rank,
    local_size,
    mesh,
    mpi_threads_supported,
    pod_size,
    pp_size,
    rank,
    replicated_sharding,
    shard_map,
    shutdown,
    size,
)
from .common.exceptions import (  # noqa: F401
    HorovodInternalError,
    HostsUpdatedInterrupt,
)
from .ops.collective_ops import (  # noqa: F401
    Adasum,
    Average,
    Max,
    Min,
    Product,
    ReduceOp,
    Sum,
    all_gather,
    all_gather_stream,
    allgather,
    allgather_async,
    allreduce,
    allreduce_async,
    allreduce_stream,
    alltoall,
    alltoall_async,
    alltoall_ragged,
    barrier,
    broadcast,
    broadcast_async,
    grouped_allreduce,
    join,
    poll,
    quantized_allreduce,
    record_wire_stats,
    reduce_scatter,
    reduce_scatter_stream,
    synchronize,
)
from .ops.compression import Compression  # noqa: F401
from .ops.fusion import allreduce_pytree, stream_order  # noqa: F401
from .parallel.functions import (  # noqa: F401
    allgather_object,
    broadcast_object,
    broadcast_optimizer_state,
    broadcast_parameters,
    broadcast_variables,
)
from .ops.flash_attention import (  # noqa: F401
    flash_attention,
    flash_ring_attention,
)
from .ops.block_diffusion import (  # noqa: F401
    block_diffusion_loss,
    block_diffusion_noise,
)
from .ops.selective_scan import selective_scan  # noqa: F401
from .ops.ssd_scan import ssd_scan  # noqa: F401
from .ops.sparse_attention import (  # noqa: F401
    index_select,
    masked_attention,
    sparse_attention,
)
from .ops.softmax_xent import (  # noqa: F401
    linear_cross_entropy,
    lm_head_loss,
)
from .parallel.optimizer import (  # noqa: F401
    DistributedOptimizer,
    OverlapMultiStepsState,
    QuantizedEFState,
    ZeroFullMultiStepsState,
    ZeroMultiStepsState,
    ZeroOverlapMultiStepsState,
    ZeroState,
    overlap_state_pspecs,
    zero3_gather_params,
    zero3_param_pspecs,
    zero3_plan,
    zero3_reshard_params,
    zero3_shard_params,
    zero_reshard_state,
    zero_state_pspecs,
)
from .parallel.sequence import (  # noqa: F401
    dense_attention,
    ring_attention,
    ulysses_attention,
)
from .parallel.sync_batch_norm import SyncBatchNorm  # noqa: F401
from .parallel.expert import (  # noqa: F401
    SwitchMoE,
    ep_split_params,
    switch_moe,
    switch_moe_ragged,
)
from . import moe  # noqa: F401  (expert-parallel MoE, docs/moe.md)
from .moe import (  # noqa: F401
    MoELayer,
    moe_apply,
    moe_ffn,
    moe_ffn_dropless,
    moe_route,
    router_bias_update,
)
from .parallel.pipeline import (  # noqa: F401
    PPSchedule,
    PP_SCHEDULES,
    build_interleaved_schedule,
    gpipe,
    gpipe_1f1b,
    interleaved_1f1b,
    pipelined_gpt_apply,
    pipelined_gpt_loss,
    pipelined_gpt_train,
    pipelined_gpt_train_1f1b,
    pp_split_blocks,
    pp_split_chunks,
)
from .parallel.tensor import (  # noqa: F401
    tp_merge_params,
    tp_shard_params,
    tp_split_params,
    tp_unshard_params,
)
from .parallel.tape import (  # noqa: F401
    DistributedGradientTape,
    allreduce_gradients,
    grad,
    value_and_grad,
)
from .common.basics import fault_counters  # noqa: F401
from .autotune import (  # noqa: F401
    AutotuneResult,
    TunedParams,
    autotune_session,
)
from .utils.timeline import start_timeline, stop_timeline  # noqa: F401
from . import plan  # noqa: F401  (composable wire-plan IR, docs/wire-plan.md)
from .plan import (  # noqa: F401
    StepPlan,
    WirePlan,
    describe_plan,
)
from . import compile  # noqa: F401  (compile-once runtime, docs/compile.md)
from .compile import precompile  # noqa: F401  (AOT warm pools)
from . import chaos  # noqa: F401  (fault injection: hvd.chaos.FaultPlan)
from . import checkpoint  # noqa: F401  (async rank-sharded save/restore)
from . import elastic  # noqa: F401  (hvd.elastic.run / State / ElasticSampler)
from . import monitor  # noqa: F401  (metrics registry / sinks / span audit)
from . import resilience  # noqa: F401  (failure-policy supervisor)
from .monitor import (  # noqa: F401
    dump_flight_record,
    metrics,
    profile_window,
    stalled_tensors,
    straggler_detector,
)

from jax.sharding import PartitionSpec as _P
from .common import basics as _basics


def data_pspec(*extra):
    """PartitionSpec splitting the leading (batch) dim over all ranks
    (``(pod, cross, local)`` on a 3-level mesh, ``HVD_AXES`` otherwise)."""
    return _P(_basics.world_axes(), *extra)


__version__ = "0.1.0"
