"""A tiny Qwen2-shaped cell for CPU tests, and a copy of the benchmark
with it added as new files."""
from __future__ import annotations

import json
import os
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
NAME = "tiny.allgather"

CONFIG = {
    "name": "tiny", "source": "test", "architecture": "qwen2",
    "hidden_size": 1024, "intermediate_size": 1024,
    "num_attention_heads": 8, "num_key_value_heads": 2,
    "num_hidden_layers": 4, "vocab_size": 2048,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000.0,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
}
TRAFFIC = {
    "agents": 6, "rounds_per_session": 3, "memory_round": 2,
    "history": {"base": 8, "jitter": 8}, "length_seed": 0,
    "task_len": 32, "gen_len": 32, "recompute_ratio": 0.15,
    "block_tokens": 32, "topology": {"kind": "all_gather"},
    "sample_requests": 6,
}
#: on the CPU over seeds 1-3 the sound run reads 0-0.011, the fp8 control
#: 0.074-0.199 and the planted faults 0.40-5.1 (bench/tests/scenarios.py)
LIMITS = {"checks": {"max_gap": {"limit": 0.03},
                     "prompts_differ": {"limit": 0}}}
#: a per-layer reader dropped in as a new file
METRIC = '''"""Rounds served in the window."""


def read(run):
    return float(len(run.window_rounds()))
'''


def tiny_cell(tmp: Path):
    """The tiny cell as ``bench.run`` loads one, without any file."""
    from bench import run
    from bench.reference import qwen2
    return run.Cell(NAME, 1, dict(CONFIG), dict(TRAFFIC), [], [],
                    json.loads(json.dumps(LIMITS)), qwen2)


def install(dest: Path) -> Path:
    """Copy ``BENCHMARK.json`` and ``bench/`` to ``dest``, link ``src``,
    and add the tiny cell as new files and new entries only."""
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    os.symlink(ROOT / "src", dest / "src")
    b = dest / "bench"
    (b / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    (b / "traffic" / "tiny-allgather.json").write_text(json.dumps(TRAFFIC))
    (b / "limits" / f"{NAME}.json").write_text(json.dumps(LIMITS))
    (b / "metrics" / "test.rounds_in_window.py").write_text(METRIC)
    bench = json.loads((dest / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": NAME, "config": "tiny",
                               "traffic": "tiny-allgather", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "test.rounds_in_window", "unit": "rounds",
        "better": "higher", "source": "program_span", "layer": "test",
        "moves": "agent_rounds_per_s", "workloads": [NAME]})
    (dest / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return dest
