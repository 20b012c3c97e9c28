"""horovod_tpu.moe: expert-parallel MoE training + serving
(docs/moe.md).

The MoE scenario family as a vertical slice of the whole stack: a top-k
gated expert FFN (:class:`MoELayer` / :func:`moe_ffn`) whose
dispatch/combine all-to-alls are first-class ``a2a`` wire plans —
validated IR, int8+error-feedback payloads on DCN-class hops, cost-model
pricing, ``MOE:*`` spans, and ``comm.moe.bytes{hop}`` accounting — over
a dedicated ``hvd_ep`` mesh axis (``hvd.init(ep_size=E)``) that is
deliberately NOT a data/world axis, so expert gradients reduce only
within their own data group. The serving half (per-expert load metrics,
hot-expert replication) lives in ``horovod_tpu/serve/``.
"""

from .layer import (  # noqa: F401
    EXPERT_LEAVES,
    MoEAux,
    MoELayer,
    MoEPlan,
    default_a2a_plan,
    ep_mean_dense_grads,
    ep_param_pspecs,
    ep_stack_params,
    moe_apply,
    moe_capacity,
    moe_ef_residuals,
    moe_ffn,
    moe_ffn_dropless,
    moe_positions,
    moe_route,
    moe_router,
    router_bias_update,
    rows_filled,
    rows_grouped,
)
