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
segment index, pool, collector, jit caches) owned by the engine and
handed over at :meth:`ReusePolicy.bind` time. A string-keyed registry
(:func:`register_policy` / :func:`get_policy`) maps legacy mode strings
onto policy classes so ``MultiAgentEngine(mode=...)`` keeps working as a
deprecated shim.
"""
from __future__ import annotations

import time
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

    One runtime per engine; ``jit`` / ``warm`` are shared across the
    policy and the engine's decode loop so shape-keyed compilations are
    paid once regardless of which side triggers them.
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
    #: tiered pool manager (eviction/offload/prefetch) — policies route
    #: persistent allocations through it and call ``ensure_resident``
    #: before reading spillable state; None only in bare-runtime tests
    manager: Optional[PoolManager] = None
    jit: dict = field(default_factory=dict)
    warm: set = field(default_factory=set)

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

    def get_jit(self, key, builder):
        if key not in self.jit:
            self.jit[key] = jax.jit(builder())
        return self.jit[key]

    def timed(self, key, fn, *args):
        """Warm up new shapes (compile excluded from timings), then time."""
        if key not in self.warm:
            jax.block_until_ready(fn(*args))
            self.warm.add(key)
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        return out, time.perf_counter() - t0


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
    assembled: Optional[tuple] = None   # (sk, sv, src, smask, priv, pmask, is_cached)
    t_restore: float = 0.0              # mirror restore spent during plan
    restore_info: Optional[dict] = None # restore ledger for RoundStats.reuse


@dataclass
class RecoveryResult:
    """Jitted-execution result: recovery logits + prefill-state cache."""

    logits: jax.Array            # [N, V] last-token logits
    cache: dict                  # prefill cache ("k"/"v" and/or ssm state)
    t_recover: float
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
        N, S = tokens.shape
        key = ("prefill", N, S)
        if key not in rt.jit:
            def f(params, toks):
                logits, cache = prefill(params, rt.cfg, toks, max_len=S)
                return logits[:, -1], cache
            rt.jit[key] = jax.jit(f)
        (logits, cache), dt = rt.timed(key, rt.jit[key], rt.params, tokens)
        return RecoveryResult(logits, cache, dt, {})


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
