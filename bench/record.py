"""What one run records: spans around the program's layers, the batches
it served, each round's reuse ledgers, compile events, the window's
bounds, and in a traced run the program's own spans.

Spans are taken on the host clock (``time.perf_counter``). In a traced run
each span is also a ``jax.profiler.TraceAnnotation`` named ``bench:<span>``,
so the reduction of the device trace can attribute device time and idle
gaps to the span they fall in.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional

SPAN_PREFIX = "bench:"


@dataclass
class Span:
    name: str
    t0: float
    t1: float = 0.0
    round: int = -1
    session: int = 0

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


@dataclass
class Batch:
    """One equal-length batch of one round: plan -> recover -> decode ->
    store, with what the output check and the readers need."""

    session: int
    round: int
    gid: str
    agents: List[str]
    tokens: object                 # numpy [N, S] prompts as served
    t_round: float                 # start of the round this batch is in
    kind: str = ""                 # "recompute" or "reuse"
    n_sel: int = 0                 # recomputed positions the program chose
    t_recover_end: float = 0.0
    passes: int = 0                # change in collector.align_passes
    outputs: object = None         # numpy [N, G] served tokens
    selected: object = None        # numpy [N, n_sel] recomputed positions

    @property
    def n(self) -> int:
        return len(self.agents)

    @property
    def prompt_len(self) -> int:
        return int(self.tokens.shape[1])


@dataclass
class Round:
    session: int
    index: int
    t0: float
    t1: float
    compression: List[float] = field(default_factory=list)
    #: the program's ``RoundStats.reuse``, whole, as plain JSON values
    reuse: dict = field(default_factory=dict)


class Recorder:
    """Collects spans, batches and compile events for one run."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.spans: List[Span] = []
        self.batches: List[Batch] = []
        self.rounds: List[Round] = []
        self.compile_events: List[tuple] = []   # (t, event, seconds)
        self.cache_misses: List[float] = []     # times of persistent misses
        #: the program's span records (``repro.serving.trace.SpanRecord``:
        #: name, t0, t1 on this clock, round, gid, attrs), in a traced run
        self.program_spans: list = []
        self.session = 0
        self.round = -1
        self._open: Dict[str, tuple] = {}

    # ------------------------------------------------------------ spans
    def _annotation(self, name: str):
        if not self.annotate:
            return None
        import jax
        ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
        ann.__enter__()
        return ann

    def open(self, name: str) -> None:
        self._open[name] = (time.perf_counter(), self._annotation(name))

    def close(self, name: str) -> Optional[Span]:
        if name not in self._open:
            return None
        t0, ann = self._open.pop(name)
        if ann is not None:
            ann.__exit__(None, None, None)
        s = Span(name, t0, time.perf_counter(), self.round, self.session)
        self.spans.append(s)
        return s

    @contextmanager
    def span(self, name: str):
        self.open(name)
        try:
            yield
        finally:
            self.close(name)

    # ------------------------------------------------- jax.monitoring
    def on_duration(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.compile_events.append((time.perf_counter(), event, secs))

    def on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            self.cache_misses.append(time.perf_counter())
