"""The readers of the program's own spans and counters, on hand-built
records and trace reductions, and the reduction that keeps the program's
spans and device 0's programs one by one."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.program import load_module
from bench.record import Round
from bench.trace import Reduced, reduce_xspace

METRICS = Path(__file__).resolve().parents[1] / "metrics"


def reader(name):
    return load_module(METRICS / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_")).read


def _rounds(*reuse):
    return [Round(0, i, float(i), i + 1.0, [], r) for i, r in enumerate(reuse)]


def test_new_programs_per_round():
    rounds = _rounds({"jit": {"new_programs": {"a": 2, "b": 1}}},
                     {"jit": [{"new_programs": {"a": 1}},
                              {"new_programs": {}}]},
                     {"jit": {"new_programs": {}}})
    run = SimpleNamespace(window_rounds=lambda: rounds)
    assert reader("jit.new_programs_per_round")(run) == pytest.approx(4 / 3)


def test_first_call_seconds_per_round():
    span = SimpleNamespace
    rec = SimpleNamespace(program_spans=[
        span(name="jit:collective_recover", t0=1.0, t1=3.5),
        span(name="jit:decode_step_paged", t0=4.0, t1=4.5),
        span(name="recover", t0=1.0, t1=3.6),
        span(name="jit:prefill", t0=-2.0, t1=-1.0),     # before the window
    ])
    run = SimpleNamespace(rec=rec, window=(0.0, 10.0),
                          window_rounds=lambda: [object()] * 2)
    assert reader("jit.first_call_s_per_round")(run) == pytest.approx(1.5)


def test_first_call_seconds_in_set_up():
    span = SimpleNamespace
    setup = SimpleNamespace(program_spans=[
        span(name="jit:prefill", t0=0.0, t1=0.5),
        span(name="recover", t0=1.0, t1=4.0),
        span(name="jit:collective_recover", t0=1.0, t1=3.5),
    ])
    run = SimpleNamespace(setup=setup)
    assert reader("jit.setup_first_call_s")(run) == pytest.approx(3.0)


def test_idle_inside_first_calls():
    # window 0-100; device busy 0-10, 40-50, 90-100: idle 70. A first call
    # 20-45 holds 20 of it, one 60-70 another 10.
    tr = Reduced(window=(0, 100), busy=[[(0, 10), (40, 50), (90, 100)]],
                 spans=[], program_spans=[("jit:a", 20, 45), ("round", 0, 90),
                                          ("jit:b", 60, 70)])
    run = SimpleNamespace(reduced=tr)
    assert reader("device.idle_in_first_call_share")(run) == \
        pytest.approx(30 / 70)


def test_token_gap_p90():
    ms = 1e6
    mods = [("jit_collective_recover", 0, 100 * ms)]
    t = 100 * ms
    for gap in [16] * 9 + [40]:               # ten steps of one batch
        mods.append(("jit_decode_step_paged", t + (gap - 10) * ms,
                     t + gap * ms))
        t += gap * ms
    mods.append(("jit_gather", t, t + ms))    # an eager op is no step
    mods.append(("jit_prefill", t + 500 * ms, t + 600 * ms))
    t += 600 * ms
    mods.append(("jit_decode_step_paged", t + 5 * ms, t + 17 * ms))
    tr = Reduced(window=(0, 10_000 * ms), busy=[[]], spans=[],
                 module_events=mods)
    gaps = sorted([16.0] * 9 + [40.0] + [17.0])
    assert reader("decode.token_gap_p90_ms")(SimpleNamespace(reduced=tr)) \
        == pytest.approx(gaps[9])


def test_no_reading_from_a_program_without_spans_or_named_programs():
    run = SimpleNamespace(
        rec=SimpleNamespace(program_spans=[]), window=(0.0, 10.0),
        setup=SimpleNamespace(program_spans=[]),
        window_rounds=lambda: _rounds({}, {}),
        reduced=Reduced(window=(0, 10), busy=[[(0, 5)]], spans=[],
                        module_events=[("jit_run", 0, 2), ("jit_f", 3, 4)]))
    for name in ("jit.new_programs_per_round", "jit.first_call_s_per_round",
                 "jit.setup_first_call_s", "device.idle_in_first_call_share",
                 "decode.token_gap_p90_ms"):
        assert reader(name)(run) is None, name


def _ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def test_reduction_keeps_program_spans_and_device_0_programs():
    line = SimpleNamespace
    data = SimpleNamespace(planes=[
        SimpleNamespace(name="/device:TPU:1", lines=[
            line(name="XLA Modules", events=[_ev("jit_prefill(3)", 0, 9)]),
            line(name="XLA Ops", events=[_ev("fusion", 0, 9)])]),
        SimpleNamespace(name="/device:TPU:0", lines=[
            line(name="XLA Modules", events=[
                _ev("jit_decode_step_paged(7)", 50, 10),
                _ev("jit_collective_recover(2)", 10, 30)]),
            line(name="XLA Ops", events=[_ev("fusion", 10, 30),
                                         _ev("fusion", 50, 10)])]),
        SimpleNamespace(name="/host:CPU", lines=[line(name="t", events=[
            _ev("bench:window", 0, 100), _ev("td:recover", 5, 40),
            _ev("td:jit:collective_recover", 6, 20), _ev("other", 1, 1)])]),
    ])
    r = reduce_xspace(data)
    assert r.spans == [("window", 0, 100)]
    assert r.program_spans == [("recover", 5, 45),
                               ("jit:collective_recover", 6, 26)]
    assert r.module_events == [("jit_collective_recover", 10, 40),
                               ("jit_decode_step_paged", 50, 60)]
    assert r.modules == {"jit_prefill": 9, "jit_decode_step_paged": 10,
                         "jit_collective_recover": 30}
