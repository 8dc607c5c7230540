"""The ``ReusePolicy`` protocol — the serving layer's policy-object API.

A policy owns one KV-reuse strategy end to end, in three phases the
engine drives every round, per gather group:

* ``plan(ctx) -> RecoveryPlan`` — host-side planning: decide what can be
  reused, restore compressed state onto the critical path, assemble the
  cached arrays the jitted pass will consume. Pure numpy / cache-entry
  bookkeeping plus any restore launches; no model execution.
* ``recover(plan, tokens) -> RecoveryResult`` — jitted execution of the
  plan: prefill / extend / PIC recovery, returning last-token logits and
  the prefill-state cache the decode loop continues from.
* ``store(ctx, cache, outputs, result, stats)`` — post-round storage:
  extract next-round segments, build Master-Mirror diffs, write the
  :class:`~repro.serving.kvpool.PagedKVPool` ledger.

Policies share a :class:`PolicyRuntime` (model substrate, sessions,
segment index, pool, collector, tracer, jitted programs) owned by the
engine and handed over at :meth:`ReusePolicy.bind` time. A string-keyed
registry (:func:`register_policy` / :func:`get_policy`) maps legacy mode
strings onto policy classes so ``MultiAgentEngine(mode=...)`` keeps
working as a deprecated shim.
"""
from __future__ import annotations

from abc import ABC, abstractmethod
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.collector import KVCollector
from repro.core.segments import PromptLayout, SegmentIndex
from repro.models import prefill
from repro.serving.kvpool import PagedKVPool
from repro.serving.pool.manager import PoolManager, Spillable
from repro.serving.state import Session
from repro.serving.trace import JitCache, Tracer


def entry_spillable(entry) -> Spillable:
    """Move a dense :class:`SegmentCacheEntry`'s k/v between tiers, in
    place — the entry object (and every index that references it) stays;
    only the array representation flips jax↔numpy."""
    def get():
        return (entry.k, entry.v)

    def put(arrs):
        entry.k, entry.v = arrs
    return Spillable(get, put)


@dataclass
class PolicyRuntime:
    """Shared serving substrate a policy executes against.

    One runtime per engine. ``programs`` is shared by the policy, the
    collector and the engine's decode loop, so each shape-keyed program
    compiles once whichever side calls it first; ``tracer`` times the
    policy's own spans (``restore``, ``store.family``).
    """

    params: dict
    cfg: ModelConfig
    gen_len: int
    ratio: float                 # recompute_ratio
    block_select: int
    sep_id: int
    sessions: Dict[str, Session]
    segment_index: SegmentIndex
    pool: PagedKVPool
    collector: KVCollector
    tracer: Tracer
    programs: JitCache
    #: tiered pool manager (eviction/offload/prefetch) — policies route
    #: persistent allocations through it and call ``ensure_resident``
    #: before reading spillable state; None only in bare-runtime tests
    manager: Optional[PoolManager] = None

    # ---- pool routing: through the manager when the engine has one ----
    def pool_alloc(self, owner: str, n_pages: int, *, persistent: bool,
                   spillable=None):
        """Allocate pool pages, through the tiered manager when present
        (pressure may then be relieved by eviction instead of raising).
        ``spillable`` registers how to move the owner's arrays between
        tiers — without it the owner can never be evicted."""
        if self.manager is not None:
            return self.manager.alloc(owner, n_pages, persistent=persistent,
                                      spillable=spillable)
        return self.pool.alloc(owner, n_pages, persistent=persistent)

    def pool_alloc_tokens(self, owner: str, n_tokens: int, *,
                          persistent: bool, spillable=None):
        return self.pool_alloc(owner, self.pool.pages_for_tokens(n_tokens),
                               persistent=persistent, spillable=spillable)

    def pool_free(self, owner: str) -> None:
        if self.manager is not None:
            self.manager.free(owner)
        else:
            self.pool.free(owner)

    def ensure_resident(self, owner: str) -> None:
        """Reload ``owner`` from the host tier if it was spilled (no-op
        without a manager or for resident owners) — policies call this
        before reading any spillable state."""
        if self.manager is not None:
            self.manager.ensure_resident(owner)


@dataclass
class RoundContext:
    """Everything a policy needs to plan one gather group's recovery."""

    round_idx: int
    gid: str                     # stable gather-group id ("g0", "g1", ...)
    agent_ids: List[str]         # group members, session order
    layouts: List[PromptLayout]
    tokens: np.ndarray           # [N, S] host-side prompt tokens

    @property
    def group_key(self) -> tuple:
        return tuple(self.agent_ids)

    @property
    def prompt_len(self) -> int:
        return int(self.tokens.shape[1])


@dataclass
class RecoveryPlan:
    """Host-side planning result, consumed by :meth:`ReusePolicy.recover`.

    ``kind`` selects the execution path: ``"recompute"`` (full batched
    prefill — also every policy's round-0 / nothing-cached fallback),
    ``"extend"`` (prefix reuse of ``prefix_len`` tokens), or ``"reuse"``
    (PIC recovery over the assembled cached arrays, serial or collective
    according to the policy)."""

    kind: str
    ctx: RoundContext
    prefix_len: int = 0
    n_sel: int = 0
    #: the budget the recovery program runs at: an upper bound of
    #: ``n_sel`` over every prompt length of the bucket
    n_sel_padded: int = 0
    #: (sk, sv, src, smask, priv, pmask, is_cached), every per-position
    #: array at the bucketed prompt length (``pic.bucket_len``)
    assembled: Optional[tuple] = None
    restore_info: Optional[dict] = None # restore ledger for RoundStats.reuse


@dataclass
class RecoveryResult:
    """Jitted-execution result: recovery logits + prefill-state cache,
    both ready on the device when ``recover`` returns."""

    logits: jax.Array            # [N, V] last-token logits
    cache: dict                  # prefill cache ("k"/"v" and/or ssm state)
    info: dict = field(default_factory=dict)


class ReusePolicy(ABC):
    """One KV-reuse strategy: plan / recover / store (see module doc)."""

    name: str = "?"
    #: PIC-style reuse needs position-independent attention KV; SSM and
    #: hybrid architectures fall back to RecomputePolicy (DESIGN.md §5).
    requires_attention: bool = False

    def __init__(self) -> None:
        self.rt: Optional[PolicyRuntime] = None

    def bind(self, rt: PolicyRuntime) -> None:
        """Attach the engine's runtime. Called once by the engine."""
        self.rt = rt

    # ------------------------------------------------------------- phases
    @abstractmethod
    def plan(self, ctx: RoundContext) -> RecoveryPlan:
        """Host-side planning for one gather group."""

    @abstractmethod
    def recover(self, plan: RecoveryPlan, tokens: jax.Array) -> RecoveryResult:
        """Jitted execution of ``plan`` over the group's prompts."""

    def store(self, ctx: RoundContext, cache: dict, outputs: np.ndarray,
              result: RecoveryResult, stats) -> None:
        """Post-round storage (default: keep nothing)."""

    # ------------------------------------------------------ shared helpers
    def _recover_recompute(self, tokens: jax.Array) -> RecoveryResult:
        """Full batched prefill — the universal fallback path."""
        rt = self.rt
        cfg, step_fn = rt.cfg, prefill
        N, S = tokens.shape

        def build():
            def f(params, toks):
                logits, cache = step_fn(params, cfg, toks, max_len=S)
                return logits[:, -1], cache
            return f
        run = rt.programs.get_jit("prefill", (N, S, cfg, step_fn), build)
        logits, cache = jax.block_until_ready(run(rt.params, tokens))
        return RecoveryResult(logits, cache, {})


# --------------------------------------------------------------------------
# Registry: legacy mode strings -> policy classes
# --------------------------------------------------------------------------
POLICIES: Dict[str, Callable[..., ReusePolicy]] = {}


def register_policy(name: str):
    """Class decorator registering a policy under a mode string."""
    def deco(cls):
        cls.name = name
        POLICIES[name] = cls
        return cls
    return deco


def get_policy(name: str, **kwargs) -> ReusePolicy:
    """Instantiate a registered policy by its mode string."""
    if name not in POLICIES:
        raise KeyError(f"unknown policy {name!r}; have {sorted(POLICIES)}")
    return POLICIES[name](**kwargs)
