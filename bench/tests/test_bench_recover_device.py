"""The reader of ``recover.device_ms_per_round`` on hand-built trace
reductions: the named recovery programs' device time over the window's
rounds, and no reading where the program gives recovery another name."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.trace import Reduced

METRICS = Path(__file__).resolve().parents[1] / "metrics"


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(modules, n_rounds):
    reduced = Reduced(window=(0, 10), busy=[[(0, 5)]], spans=[],
                      modules=modules)
    return SimpleNamespace(reduced=reduced,
                           window_rounds=lambda: [object()] * n_rounds)


def test_recovery_device_time_per_round():
    read = _reader("recover.device_ms_per_round")
    mods = {"jit_collective_recover": 1.4e9, "jit_decode_step_paged": 9e9}
    assert read(_run(mods, 7)) == pytest.approx(200.0)


@pytest.mark.parametrize("modules,n_rounds", [
    ({"jit_run": 2.8e9, "jit_f": 3.4e9}, 7),   # programs left unnamed
    ({"jit_collective_recover": 1e9}, 0),      # no round in the window
])
def test_no_reading_without_named_recovery_or_rounds(modules, n_rounds):
    read = _reader("recover.device_ms_per_round")
    assert read(_run(modules, n_rounds)) is None
    assert read(SimpleNamespace(reduced=None,
                                window_rounds=lambda: [object()])) is None
