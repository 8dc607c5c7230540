from repro.serving.engine import MODES, MultiAgentEngine, ServingEngine
from repro.serving.kvpool import Allocation, PagedKVPool, PoolExhausted
from repro.serving.loop import (
    ContinuousEngine,
    ContinuousResult,
    Phase,
    PhaseCost,
    StepEvent,
    StepScheduler,
    WorkItem,
)
from repro.serving.planner import RoundPlan, RoundPlanner
from repro.serving.pool import (
    EvictionPolicy,
    FamilyCostAware,
    HostTier,
    LRUByRound,
    PoolLedger,
    PoolManager,
    PrefetchPlanner,
    Spillable,
    get_eviction_policy,
)
from repro.serving.policies import (
    POLICIES,
    PICPolicy,
    PolicyRuntime,
    PrefixCachePolicy,
    RecomputePolicy,
    RecoveryPlan,
    RecoveryResult,
    ReusePolicy,
    RoundContext,
    TokenDancePolicy,
    get_policy,
    register_policy,
)
from repro.serving.round_kv import DenseRoundKV, PagedRoundKV, round_kv
from repro.serving.scheduler import (
    ServiceTimes,
    max_agents_under_slo,
    service_times_from_stats,
    simulate_round_latency,
)
from repro.serving.state import RoundStats, Session
from repro.serving.trace import Tracer

__all__ = [
    # engine
    "MODES",
    "MultiAgentEngine",
    "ServingEngine",
    "RoundStats",
    "Session",
    # spans of the serving engine
    "Tracer",
    # policies
    "POLICIES",
    "PICPolicy",
    "PolicyRuntime",
    "PrefixCachePolicy",
    "RecomputePolicy",
    "RecoveryPlan",
    "RecoveryResult",
    "ReusePolicy",
    "RoundContext",
    "TokenDancePolicy",
    "get_policy",
    "register_policy",
    # planner + capacity model
    "RoundPlan",
    "RoundPlanner",
    "ServiceTimes",
    "max_agents_under_slo",
    "service_times_from_stats",
    "simulate_round_latency",
    # pool
    "Allocation",
    "PagedKVPool",
    "PoolExhausted",
    # tiered pool manager (ISSUE 6)
    "EvictionPolicy",
    "FamilyCostAware",
    "HostTier",
    "LRUByRound",
    "PoolLedger",
    "PoolManager",
    "PrefetchPlanner",
    "Spillable",
    "get_eviction_policy",
    # round-KV views (ISSUE 7)
    "DenseRoundKV",
    "PagedRoundKV",
    "round_kv",
    # continuous serving loop (ISSUE 9)
    "ContinuousEngine",
    "ContinuousResult",
    "Phase",
    "PhaseCost",
    "StepEvent",
    "StepScheduler",
    "WorkItem",
]
