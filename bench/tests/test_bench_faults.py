"""The output check on a tiny cell on the CPU: the sound run passes, its
fp8 control reads above the limit and comes out not correct, and every fault planted
in the timed path turns ``correct`` false (``scenarios.py``)."""
import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def test_control_and_planted_faults_fail_the_check(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(HERE / "scenarios.py"), str(tmp_path), "1"],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    runs = {r["run"]: r for r in
            (json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{"))}
    assert set(runs) == {"sound", "state_unchanged", "half_batch",
                         "token_altered"}
    sound = runs["sound"]
    limit = sound["checks"]["max_gap"]["limit"]
    assert sound["correct"], sound
    control = sound["controls"]["fp8"]
    assert not control["correct"], control
    assert control["checks"]["max_gap"]["value"] > limit \
        > sound["checks"]["max_gap"]["value"], sound
    for name in ("state_unchanged", "half_batch", "token_altered"):
        assert not runs[name]["correct"], runs[name]
        assert runs[name]["checks"]["max_gap"]["value"] > limit
