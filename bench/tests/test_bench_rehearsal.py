"""The harness end to end on the CPU at a tiny size, with a configuration,
a traffic mix and a per-layer metric that exist only as new files; and
its refusals: a real cell without a TPU, a checkout without the program."""
import json
import os
import shutil
import subprocess
import sys

from bench.tests.tiny import NAME, install

SEED = str(2**31 + 7)


def _run(cwd, *args, cpu_rehearsal=True):
    cmd = [sys.executable, "bench/run.py", *args]
    if cpu_rehearsal:
        cmd.append("--cpu-rehearsal")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(cmd, cwd=cwd, capture_output=True, text=True,
                          env=env, timeout=600)


def _result(proc):
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(out)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in out, key
    assert out["device"]["platform"] == "cpu"
    return out


def test_tiny_cell_runs_from_new_files_only(tmp_path):
    root = install(tmp_path / "checkout")
    out = _result(_run(root, "--workload", NAME, "--seed", SEED,
                       "--seconds", "0.1", "--trace", "0"))
    assert out["failed"] == 0 and out["attempted"] == 6
    assert isinstance(out["correct"], bool)
    assert {"agent_rounds_per_s", "ttft_p90_s", "setup_s"} <= \
        set(out["metrics"])
    assert out["checks"]["max_gap"]["limit"] == 0.03
    assert out["checks"]["prompts_differ"] == {"value": 0, "limit": 0}

    out = _result(_run(root, "--workload", NAME, "--seed", SEED,
                       "--seconds", "0.1", "--trace", "1"))
    # the existing per-layer metrics list their own cells; only the new
    # one names this cell
    assert out["metrics"] == {"test.rounds_in_window":
                              {"value": 1.0, "unit": "rounds"}}
    assert "breakdown" in out and "window_s" in out["device"]


def test_real_cell_refuses_the_cpu(tmp_path):
    root = install(tmp_path / "checkout")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = bench["workloads"][0]["name"]
    proc = _run(root, "--workload", cell, "--seed", "1", "--seconds", "1",
                "--trace", "0", cpu_rehearsal=False)
    assert proc.returncode != 0
    assert not proc.stdout.strip()


def test_benchmark_files_alone_do_not_run(tmp_path):
    root = install(tmp_path / "checkout")
    os.unlink(root / "src")
    for p in root.iterdir():
        if p.name not in ("bench", "BENCHMARK.json"):
            shutil.rmtree(p)
    proc = _run(root, "--workload", NAME, "--seed", "1", "--seconds", "0.1",
                "--trace", "0")
    assert proc.returncode != 0
    assert not proc.stdout.strip()
