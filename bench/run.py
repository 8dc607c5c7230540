"""TokenDance agent-round benchmark: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic, per-layer readers and output-check limits are the files
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``,
``bench/metrics/<metric>.py`` and ``bench/limits/<cell>.json``, and its
configuration's ``architecture`` names its reference and the adapter
that configures the program, ``bench/reference/<architecture>.py`` and
``bench/adapters/<architecture>.py``.

A run needs one TPU chip per ``chips`` of the cell and fails, printing no
result, where JAX finds fewer. Set-up (``setup_s``, from process start):

1. The weights are drawn on the device from ``--seed``.
2. A throw-away engine serves session 0 of this seed, every round of it.
   The program builds each jitted program once per process and every
   later engine of the process runs it, so this builds every program the
   session uses: those of each round's prompt length, and those whose
   shapes follow from what the tokens make the program do (how many
   blocks recovery recomputed and restore copies, how many differ
   between a Master and its Mirrors), so that no program of the first
   session is traced, compiled or loaded inside the window. On a
   checkout's first run they compile, into the persistent compilation
   cache (``bench/.cache/jax``); later runs load them from there. The
   memory metrics are read on this engine: ``bytes_in_use`` before its
   round 0, and ``bytes_in_use`` and ``peak_bytes_in_use`` after its
   round ``memory_round``, when the process has served nothing else.
3. A fresh engine serves rounds 0 (recompute) and 1 (full family restore
   and collective recovery) of session 0.

The window starts at round 2 and serves rounds back to back until
``--seconds`` have passed, then finishes the round in progress. A session
that runs out of rounds is followed by a fresh engine on the next
session's traffic. After the window, the served tokens of a sample of
its requests are compared with a float32 replay of the served semantics
(``bench/correct.py``, ``bench/reference/tokendance.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE = BENCH / ".cache"
TRACES = ROOT / "chiprun_out" / "bench_traces"
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench.program import ADAPTERS, load_module  # noqa: E402
from bench.traffic.generator import generate_session, load_traffic  # noqa: E402


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# the cell, found by name
# --------------------------------------------------------------------------
@dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    limits: dict
    reference: object
    adapters: Path = ADAPTERS


def load_cell(name: str, references: Path = BENCH / "reference",
              adapters: Path = ADAPTERS) -> Cell:
    """The cell ``name``, its architecture's reference and adapter looked
    up in the directories ``references`` and ``adapters``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    cfg = json.loads((ROOT / conf["file"]).read_text())
    traffic = load_traffic(BENCH / "traffic" / f"{w['traffic']}.json")
    limits = json.loads((BENCH / "limits" / f"{name}.json").read_text())

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    reference = load_module(
        Path(references) / f"{cfg['architecture']}.py",
        f"bench_reference_{cfg['architecture']}")
    return Cell(name, int(w["chips"]), cfg, traffic,
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)], limits,
                reference, Path(adapters))


# --------------------------------------------------------------------------
# JAX and the chip
# --------------------------------------------------------------------------
def start_jax(cell: Cell, rehearsal: bool):
    """Import JAX with the benchmark's persistent cache; refuse to run
    without ``cell.chips`` accelerators unless rehearsing on the CPU. The
    TPU runtime's logs go inside the checkout too, not to ``/tmp``."""
    os.environ.setdefault("TPU_LOG_DIR", str(CACHE / "tpu_logs"))
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    if not rehearsal and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        log(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        sys.exit(3)
    return jax, devs[0]


def _barrier(jax) -> None:
    """Wait for every queued device computation (one stream per chip)."""
    import jax.numpy as jnp
    jax.block_until_ready(jnp.zeros(()) + 1)


def memory(jax, dev) -> dict:
    gc.collect()
    _barrier(jax)
    return dev.memory_stats() or {}


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------
@dataclass
class View:
    """What a per-layer reader sees."""

    rec: object
    window: tuple
    traffic: dict
    dims: dict
    device_kind: object
    reduced: object = None
    #: the record of set-up's warm-up session
    setup: object = None

    def _inside(self, t0, t1) -> bool:
        return self.window[0] <= t0 and t1 <= self.window[1]

    def window_rounds(self):
        return [r for r in self.rec.rounds if self._inside(r.t0, r.t1)]

    def window_spans(self):
        return [s for s in self.rec.spans if self._inside(s.t0, s.t1)]

    def window_batches(self):
        return [b for b in self.rec.batches if self.window[0] <= b.t_round]


_LISTENING = []


def _listen(jax, rec) -> None:
    """Route ``jax.monitoring`` events to ``rec`` (listeners are
    registered once per process)."""
    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(
            lambda *a, **k: _LISTENING[-1].on_duration(*a, **k))
        jax.monitoring.register_event_listener(
            lambda *a, **k: _LISTENING[-1].on_event(*a, **k))
    _LISTENING.append(rec)


def measure(cell: Cell, seed: int, seconds: float, trace: bool,
            rehearsal: bool, controls: tuple = ()) -> dict:
    """One run; ``controls`` adds the readings and verdicts of
    lower-precision replays (``bench/calibrate.py``), which benchmark runs
    never compute."""
    jax, dev = start_jax(cell, rehearsal)
    from bench import correct
    from bench.program import Server
    from bench.record import Recorder

    rec = Recorder(annotate=trace)
    _listen(jax, rec)
    tr = cell.traffic
    V = cell.cfg["vocab_size"]

    weights = jax.block_until_ready(cell.reference.make_weights(seed, cell.cfg))
    w_bytes = cell.reference.weight_bytes(weights)

    def new_server(i, rec=rec):
        return Server(cell.cfg, tr, weights, generate_session(tr, V, seed, i),
                      rec, i, trace=trace, adapters=cell.adapters)

    t_warm = time.perf_counter()
    setup = Recorder()
    warm = new_server(0, setup)
    before = memory(jax, dev).get("bytes_in_use")
    mem = {}
    while not warm.done:
        warm.run_round()
        if warm.round_idx - 1 == tr["memory_round"]:
            mem = memory(jax, dev)
    warm.close()
    del warm
    log(f"bench: warm-up session served in "
        f"{time.perf_counter() - t_warm:.3f}s; bytes in use {before} before "
        f"round 0, {mem.get('bytes_in_use')} after round "
        f"{tr['memory_round']}, peak {mem.get('peak_bytes_in_use')}")
    for sp in setup.program_spans:
        if sp.name.startswith("jit:"):
            log(f"bench: warm-up round {sp.round} {sp.name} {sp.dur:.4f}s")
    srv = new_server(0)
    for _ in range(2):
        srv.run_round()
    _barrier(jax)
    setup_s = time.perf_counter() - T_START
    log(f"bench: set-up {setup_s:.3f}s (weights {w_bytes / 2**30:.3f} GiB)")

    trace_dir = TRACES / f"{cell.name}-{seed}"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    t0 = time.perf_counter()
    rec.open("window")
    sessions = 1
    while True:
        if srv.done:
            srv.close()
            srv = new_server(sessions)
            sessions += 1
        srv.run_round()
        if time.perf_counter() - t0 >= seconds:
            break
    _barrier(jax)
    rec.close("window")
    t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    window = (t0, t1)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    view = View(rec, window, tr, cell.reference.dims(cell.cfg),
                dev.device_kind if dev.platform == "tpu" else None,
                setup=setup)
    batches = view.window_batches()
    reqs = correct.finished_requests(batches)
    attempted = sum(b.n for b in batches)
    G = tr["gen_len"]
    failed = attempted - sum(
        1 for r in reqs if r.served.shape == (G,)
        and int(r.served.min()) >= 0 and int(r.served.max()) < V)
    ttft = sorted(b.t_recover_end - b.t_round for b in batches
                  for _ in range(b.n))
    misses = sum(1 for t in rec.cache_misses if t0 <= t <= t1)
    log(f"bench: window {t1 - t0:.3f}s, {len(view.window_rounds())} rounds, "
        f"{attempted} agent-rounds, {sessions} session(s), "
        f"{misses} persistent-cache misses, "
        f"{sum(s for t, _, s in rec.compile_events if t0 <= t <= t1):.3f}s "
        f"of compile events")
    for b in batches:
        log(f"bench: round {b.round} {b.gid} N={b.n} S={b.prompt_len} "
            f"{b.kind} n_sel={b.n_sel} ttft={b.t_recover_end - b.t_round:.4f}s")
    log(f"bench: ttft samples {len(ttft)}")

    metrics = {}
    if not trace:
        vals = {
            "agent_rounds_per_s": attempted / (t1 - t0),
            "ttft_p90_s": _quantile(ttft, 0.9),
            "setup_s": setup_s,
        }
        if before is not None and mem:
            n = tr["agents"]
            vals["resident_mib_per_agent"] = \
                (mem["bytes_in_use"] - before) / n / 2**20
            vals["peak_above_weights_gib"] = \
                (mem["peak_bytes_in_use"] - w_bytes) / 2**30
        for m in cell.end_to_end:
            if m["name"] in vals:
                metrics[m["name"]] = {"value": vals[m["name"]],
                                      "unit": m["unit"]}
    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    if trace:
        from bench import trace as tracemod
        view.reduced = tracemod.load(trace_dir)
        device["busy_s"] = view.reduced.busy_s()
        device["window_s"] = view.reduced.window_s
        breakdown = {"device_ops": tracemod.top(view.reduced.modules),
                     "idle_gaps": [list(g) for g in view.reduced.idle_gaps()]}
        for name, secs in sorted(view.reduced.idle_by_span().items(),
                                 key=lambda kv: -kv[1]):
            log(f"bench: idle {secs:.4f}s in {name}")
        for name, secs in view.reduced.idle_gaps():
            log(f"bench: idle gap {secs:.4f}s in {name}")
        recorded = sum(t0 <= s.t0 and s.t1 <= t1 for s in rec.program_spans)
        log(f"bench: {len(view.reduced.program_spans)} program spans in the "
            f"trace, {recorded} recorded in the window, "
            f"{len(view.reduced.module_events)} programs run on device 0")
        for m in cell.per_layer:
            mod = load_module(BENCH / "metrics" / f"{m['name']}.py",
                              "bench_metric_" + m["name"].replace(".", "_"))
            v = mod.read(view)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- the output check, after the window and the memory reading ----
    srv.close()
    del srv
    gc.collect()
    log(f"bench: {memory(jax, dev).get('bytes_in_use')} bytes in use "
        f"before the reference")
    t_ref = time.perf_counter()
    sample = correct.sample(reqs, tr["sample_requests"], seed)
    got = correct.compare(cell.reference, weights, cell.cfg, tr, seed,
                          correct.finished_requests(rec.batches), sample,
                          controls)
    log(f"bench: reference over {len(sample)} requests "
        f"({len(sample) * G} served tokens, longest prompt "
        f"{max((len(r.prompt) for r in sample), default=0)}) took "
        f"{time.perf_counter() - t_ref:.1f}s; first-token gap "
        f"{got['program']['max_gap_first']:.6f}; "
        f"{got['program']['ties']} agent-rounds with a choice of blocks; "
        f"{got['program']['selections_differ']} recomputed other blocks than "
        f"the reference, whose ranking put them apart by "
        f"{[round(m, 5) for m in got['program']['margins']]}")
    for s_, r_, a_, g_, g0_ in got["program"]["gaps"]:
        log(f"bench: gap session {s_} round {r_} {a_}: {g_:.6f} "
            f"(first token {g0_:.6f})")

    def judge(read):
        checks = {key: {"value": read[key], "limit": lim["limit"]}
                  for key, lim in cell.limits["checks"].items()}
        return all(c["value"] <= c["limit"] for c in checks.values()), checks

    ok, checks = judge(got["program"])
    ok = ok and attempted > 0 and failed == 0 and bool(sample)
    out = {"correct": ok, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if controls:
        out["controls"] = {}
        for q in controls:
            c_ok, c_checks = judge(got[q])
            out["controls"][q] = {"correct": c_ok, "checks": c_checks}
    out["checks"] = checks
    for key, c in checks.items():
        log(f"check {key}: {c['value']!r} limit {c['limit']!r}")
    return out


def _quantile(xs, q: float) -> float:
    """Nearest-rank quantile of a sorted list."""
    if not xs:
        return float("nan")
    i = max(0, min(len(xs) - 1, int(-(-q * len(xs) // 1)) - 1))
    return float(xs[i])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on whatever JAX finds (tests only)")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    out = measure(cell, args.seed, args.seconds, bool(args.trace),
                  args.cpu_rehearsal)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
