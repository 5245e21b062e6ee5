from elasticsearch_tpu.threadpool.pool import (
    EsRejectedExecutionError, FixedExecutor, ThreadPool, pool_for_request,
    tier_for_request,
)
from elasticsearch_tpu.threadpool.scheduler import (
    AdaptiveDispatchScheduler, activate_tier, current_tier,
    default_scheduler, scheduler_stats, serving_dispatch,
)

__all__ = ["AdaptiveDispatchScheduler",
           "EsRejectedExecutionError", "FixedExecutor", "ThreadPool",
           "activate_tier", "current_tier",
           "default_scheduler", "pool_for_request", "scheduler_stats",
           "serving_dispatch", "tier_for_request"]
