"""Public-API snapshot: the exported surface of ``repro.serving`` and
``repro.core`` is pinned here so future PRs cannot silently break the
policy-object serving API. Additions require updating this snapshot
(deliberate, reviewed); removals/renames fail loudly."""
import repro.core as core
import repro.serving as serving

SERVING_API = {
    # engine
    "MODES",
    "MultiAgentEngine",
    "ServingEngine",
    "RoundStats",
    "Session",
    # spans of the serving engine
    "Tracer",
    # policy objects
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
}

CORE_API = {
    # collector
    "CollectiveResult",
    "KVCollector",
    "ReusePlan",
    "group_compatible",
    # diff store
    "BLOCK_TOKENS",
    "FamilyPack",
    "MasterCache",
    "MirrorDiff",
    "MirrorHandle",
    "build_mirror",
    "build_round_family",
    "compression_stats",
    "pack_family",
    "similarity_master",
    # pic
    "PICResult",
    "PagedHistory",     # paged attention consumer (ISSUE 5)
    "align_cached_keys",
    "n_sel_for",
    "pic_prefill",
    # restore
    "dense_restore",
    "dense_restore_paged",
    "fused_restore_family_paged",
    "fused_restore_family_shared",
    "fused_restore_paged",
    # rounds + topologies
    "AgentState",
    "AllGather",
    "AllGatherTrace",
    "GatherTopology",
    "Round",
    "SubsetGather",
    "generate_trace",
    "round_prompt",
    # segments
    "PRIVATE",
    "SHARED",
    "TASK",
    "PromptLayout",
    "Segment",
    "SegmentCacheEntry",
    "SegmentIndex",
    "Span",
    "build_prompt",
    "segment_hash",
    "split_prompt",
}


def test_serving_exports_match_snapshot():
    assert set(serving.__all__) == SERVING_API
    missing = [n for n in serving.__all__ if not hasattr(serving, n)]
    assert not missing, missing


def test_core_exports_match_snapshot():
    import types
    exported = {n for n in dir(core) if not n.startswith("_")
                and not isinstance(getattr(core, n), types.ModuleType)}
    assert exported == CORE_API, {
        "unexpected": sorted(exported - CORE_API),
        "missing": sorted(CORE_API - exported)}


def test_modes_tuple_matches_registry():
    assert serving.MODES == ("recompute", "prefix", "pic", "tokendance")
    assert set(serving.MODES) == set(serving.POLICIES)
