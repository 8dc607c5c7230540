"""Trace reduction against a small trace recorded on one TPU v5e chip
(``record_trace.py``), and the operation and byte counts of one decode
step at two models' widths against counts worked out by hand."""
import json
from pathlib import Path

import pytest

from bench import roofline
from bench.reference import qwen2
from bench.trace import Reduced, overlap, union

HERE = Path(__file__).resolve().parent
CONFIGS = HERE.parents[0] / "configs"
TRACE = HERE / "data" / "v5e_trace.xplane.pb"


def test_union_overlap_and_idle_gaps():
    merged = union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert merged == [(0, 3), (5, 9)]
    assert overlap(merged, 2, 6) == 2
    r = Reduced(window=(0, 10), busy=[merged],
                spans=[("window", 0, 10), ("round", 0, 10), ("store", 3, 5)])
    assert r.busy_s() == pytest.approx(7e-9)
    assert r.idle_gaps() == [("store", pytest.approx(2e-9)),
                             ("round", pytest.approx(1e-9))]
    assert r.busy_in("store") == 0


def test_recorded_trace():
    from bench.trace import find_xplane, load
    assert find_xplane(HERE / "data") == TRACE
    r = load(HERE / "data")
    assert len(r.busy) == 1                      # one chip
    names = [n for n, _, _ in r.spans]
    assert names.count("round") == 2 and names.count("decode") == 2
    assert names.count("store") == 2 and names.count("window") == 1
    assert 0.040 < r.window_s < 0.060
    # six runs of one program, each about 90 us of device time (a 2048^3
    # bf16 matmul is 87 us at 197 TFLOP/s)
    assert list(r.modules) == ["jit__lambda"]
    assert 6 * 85e3 < r.modules["jit__lambda"] < 6 * 100e3
    # device events sit about a millisecond early against the host spans
    # in this trace, so the first run falls before the window opens
    assert 0.2e-3 < r.busy_s() < 0.6e-3
    assert r.busy_in("decode") + r.busy_in("store") <= r.busy_s() + 1e-9
    # the two longest idle gaps are the 20 ms host sleeps in the store spans
    top = r.idle_gaps()[:2]
    assert [n for n, _ in top] == ["store", "store"]
    assert all(0.019 < s < 0.03 for _, s in top)
    assert sum(r.idle_by_span().values()) == pytest.approx(
        r.window_s - r.busy_s(), rel=1e-6)


def _dims(name):
    return qwen2.dims(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_decode_step_counts_by_hand():
    # qwen2.5-7b cut to 16 layers, 16 agents, new token at position 704
    layer = 3584 * 3584 * 2 + 2 * 3584 * 512 + 3 * 3584 * 18944
    assert layer == 233_046_016
    weights = 16 * layer + 3584 * 152064               # 4,273,733,632
    kv_token = 16 * 2 * 4 * 128 * 2                    # 32 KiB
    flops = 2 * 16 * weights + 16 * 4 * 16 * 28 * 128 * 705
    nbytes = 2 * weights + 16 * 705 * kv_token + 16 * kv_token
    assert (flops, nbytes) == (139_346_837_504, 8_917_614_592)
    assert roofline.decode_step_cost(_dims("qwen2.5-7b"), 16, 705) == \
        (flops, nbytes)
    # Qwen2.5-14B's widths cut to 12 layers, a 10-agent batch, position 832
    layer = 5120 * 5120 * 2 + 2 * 5120 * 1024 + 3 * 5120 * 13824
    weights = 12 * layer + 5120 * 152064               # 4,081,582,080
    kv_token = 12 * 2 * 8 * 128 * 2                    # 48 KiB
    flops = 2 * 10 * weights + 10 * 4 * 12 * 40 * 128 * 833
    nbytes = 2 * weights + 10 * 833 * kv_token + 10 * kv_token
    assert (flops, nbytes) == (83_678_822_400, 8_573_091_840)
    d14 = dict(L=12, D=5120, H=40, KV=8, hd=128, F=13824, V=152064)
    assert roofline.decode_step_cost(d14, 10, 833) == (flops, nbytes)


def test_recovery_counts_never_exceed_a_full_prefill():
    d = _dims("qwen2.5-7b")
    S, bt = 704, 32
    full = roofline.prefill_flops(d, 16, S)
    every = roofline.recovery_flops(d, 16, S, S, bt)
    # all blocks selected: the check layers and the rest cover every
    # position once, the same work as a prefill
    assert every == full
    part = roofline.recovery_flops(
        d, 16, S, roofline.n_sel_for_blocks(S // bt, 1, 0.15, bt), bt)
    assert part < full
    assert roofline.n_sel_for_blocks(22, 1, 0.15, bt) == (1 + 4) * bt
    # nothing cached: every block, and no more
    assert roofline.n_sel_for_blocks(4, 4, 0.15, bt) == 4 * bt


def test_peaks_know_the_chip_and_refuse_others():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
