"""horovod_tpu.plan: the composable wire-plan IR (docs/wire-plan.md).

A collective is a :class:`WirePlan` — an ordered list of :class:`Leg`\\ s,
each naming a mesh level (ICI ring / DCN cross / pod axis), a primitive
(reduce-scatter, all-gather, all-to-all, psum), a wire dtype (payload /
blockwise-int8 with error-feedback slot), and a stream assignment — plus:

* a **compiler** (:mod:`~horovod_tpu.plan.compiler`) lowering a validated
  plan to the existing jax primitives, with trace-time wire accounting
  and overlap instrumentation built into every leg
  (:mod:`~horovod_tpu.plan.accounting`);
* a **planner** (:mod:`~horovod_tpu.plan.planner`) deriving the default
  plan from (mesh shape, quantized, zero_stage, overlap, hierarchical),
  so today's knob combinations are points in one plan space —
  :func:`describe_plan` is the debug view, and :func:`encode_tuned` /
  :func:`decode_tuned` the autotuner's compact search encoding.

Every public collective (``hvd.allreduce`` / ``reduce_scatter`` /
``all_gather`` and their ``*_stream`` variants) routes through this
compiler; the bespoke hand-composed paths it replaced live on only as
leg lowering rules in :mod:`~horovod_tpu.plan.compiler`.

The plan space is also a **priced design space** (docs/cost-model.md):
:mod:`~horovod_tpu.plan.cost` gives every link class a calibrated
``(bandwidth, latency, quant-rate)`` triple
(:mod:`~horovod_tpu.plan.calibrate` measures them with a
microbenchmark sweep stored beside the autotune cache) and prices any
validated plan analytically; :func:`shortlist` enumerates + prices the
legal plan space for a knob set into the ranked candidate list the GP
autotuner warm-starts from (``autotune_session(warm_start=K)``).
"""

from .ir import (  # noqa: F401
    ALL_GATHER,
    ALL_TO_ALL,
    DCN,
    FLAT,
    ICI,
    INT8,
    PAYLOAD,
    POD,
    PSUM,
    REDUCE_SCATTER,
    SEND,
    Leg,
    PlanError,
    WirePlan,
)
from .accounting import (  # noqa: F401
    WireStats,
    bench_gbps,
    kv_span,
    modeled_wire_ms,
    moe_span,
    record_wire_stats,
)
from .planner import (  # noqa: F401
    PricedPlan,
    StepPlan,
    a2a_plan,
    decode_tuned,
    derive_a2a,
    derive_all_gather,
    derive_allreduce,
    derive_reduce_scatter,
    describe_plan,
    encode_tuned,
    enumerate_tuned,
    ep_a2a_level,
    flat_plan,
    derive_kv_migrate,
    derive_send,
    kv_migrate_level,
    kv_migrate_plan,
    pp_bubble_bound,
    pp_send_level,
    predict_a2a_bytes,
    predict_kv_migrate_bytes,
    predict_leg_bytes,
    quantized_allreduce_plan,
    send_plan,
    shortlist,
    tree_allreduce_plan,
    zero_all_gather_plan,
    zero_reduce_scatter_plan,
)
from .cost import (  # noqa: F401
    CostModel,
    LinkClass,
    PlanCost,
    StepCost,
    price_a2a,
    price_kv_migrate,
    price_plan,
    price_send,
    price_step,
)
from .calibrate import (  # noqa: F401
    Calibration,
    calibrate_links,
    get_cost_model,
    load_calibration,
)
from . import compiler  # noqa: F401
