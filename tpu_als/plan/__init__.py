"""Execution planner: roofline-ranked, probe-confirmed, persisted.

``tpu_als.plan.planner`` resolves ExecutionPlan components for every
dispatch site in the stack; ``tpu_als.plan.cache`` is the on-disk,
schema-validated autotune cache behind it (jax-free — bench.py loads it
standalone).  See docs/planner.md.
"""

from tpu_als.plan.cache import PlanCacheCorrupt, SCHEMA_VERSION  # noqa: F401
from tpu_als.plan.planner import (  # noqa: F401
    AUTOTUNE_ENV,
    DEFAULT_LIVE_CADENCE,
    GATHER_CANDIDATES,
    ExecutionPlan,
    armed,
    autotune_enabled,
    clear,
    gather_model,
    invalidate_kernel_config,
    mode,
    plan_key,
    resolve_execution_plan,
    resolve_gather_strategy,
    resolve_kernel_config,
    resolve_live_cadence,
    resolve_serving_buckets,
    resolve_tenant_plan,
    resolve_topk,
    resolve_training,
    shape_class,
    training_model,
)
