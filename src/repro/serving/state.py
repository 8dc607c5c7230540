"""Shared serving state: per-agent sessions and per-round statistics.

Lives in its own module so the engine (round loop), the policy objects
(``serving/policies/``) and the planner can all import it without cycles.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

import jax
import numpy as np

from repro.core.rounds import AgentState


@dataclass
class RoundStats:
    round_idx: int
    mode: str                    # the serving policy's registry name
    n_agents: int
    prompt_len: int
    # seconds, the round's sums of the engine tracer's spans; a new
    # shape's first call (trace, compile, dispatch) is inside them
    t_recover: float = 0.0       # ``recover``: prefill / PIC recovery
    t_restore: float = 0.0       # ``restore``: mirror restore in plan()
    t_decode: float = 0.0        # ``decode``
    t_store: float = 0.0         # ``store``: diff build / segment extraction
    persistent_bytes: int = 0    # cache state surviving the round
    transient_peak_bytes: int = 0
    outputs: Optional[np.ndarray] = None      # [N, G] generated tokens
    first_logits: Optional[np.ndarray] = None  # [N, V] recovery logits
    #: reuse ledgers by name; "jit" = {"new_programs": {name: n}}
    reuse: dict = field(default_factory=dict)
    admission: Optional[dict] = None          # RoundPlanner decision

    @property
    def t_round(self) -> float:
        return self.t_recover + self.t_restore + self.t_decode + self.t_store

    def merge_reuse(self, key: str, value) -> None:
        """Record a reuse-ledger entry. Single-gather-group rounds (the
        All-Gather default) write the value directly — identical to the
        pre-policy engine; multi-group rounds accumulate a list."""
        if key not in self.reuse:
            self.reuse[key] = value
        elif isinstance(self.reuse[key], list):
            self.reuse[key].append(value)
        else:
            self.reuse[key] = [self.reuse[key], value]


@dataclass
class Session:
    agent_id: str
    state: AgentState
    # prefix policy: the agent's dense cache + the prompt it was built for
    dense_k: Optional[jax.Array] = None       # [L, S, KV, hd]
    dense_v: Optional[jax.Array] = None
    prompt_tokens: Optional[np.ndarray] = None
    # pic / tokendance: history segment cache (dense, or paged when the
    # engine keeps restored families paged end-to-end)
    hist_entry: Optional[object] = None   # SegmentCacheEntry | PagedSegmentCacheEntry
    # tokendance: compressed persistent state
    mirror: Optional[object] = None       # MirrorHandle
    is_master: bool = False
    family: Optional[tuple] = None        # Master-family member tuple
    hist_pending: Optional[tuple] = None   # (hist span len, own-output sid)
