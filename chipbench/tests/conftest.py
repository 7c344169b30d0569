"""Test scaffolding: a copy of the benchmark with a tiny configuration and
tiny traffic mixes added as new files, runnable on the CPU."""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_ARCH = {
    "name": "olmo-tiny", "family": "dense", "source": "test",
    "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
    "d_ff": 128, "vocab_size": 2048, "activation": "swiglu",
    "norm": "nonparam_ln", "tie_embeddings": True, "rope_theta": 10000.0,
    "attention_class": "quadratic", "dtype": "bfloat16",
    "moment_dtype": "float32"}
TINY_CONFIG = {"source": "test", "reference": "olmo", "vocab_size": 2000,
               "reduced": [], "arch": TINY_ARCH}
TINY_SERVE = {
    "kind": "serve", "schedule_seed": 1,
    "engine": {"max_slots": 4, "max_len": 96},
    "arrivals": {"process": "open", "rate_per_s": 6.0, "gamma_shape": 1.0},
    "prompt_len": {"dist": "lognormal", "median": 16, "sigma": 1.0,
                   "snap": [8, 16, 32]},
    "output_len": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                   "clip": [4, 32]},
    "check": {"requests": 8}}
TINY_TRAIN = {
    "kind": "train", "batch": 2, "seq": 32, "remat": "full",
    "check_steps": 3,
    "optimizer": {"lr": 4e-4, "warmup_steps": 0, "total_steps": 100000,
                  "min_lr_ratio": 0.1, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                  "weight_decay": 0.1, "grad_clip": 1.0}}
TINY_METRIC = '''"""Requests attempted in the window (a test's metric)."""


def read(run):
    return float(run.attempted)
'''


def add_tiny(root: Path, serve_limits=None, train_limits=None) -> Path:
    """Copy the benchmark under ``root`` and add, as new files only, a
    tiny configuration, two tiny mixes, their limits, two cells and one
    per-layer metric; the training cell reports ``train_tokens_per_s``,
    whose reader the benchmark keeps for a training cell.  Returns the
    copy's benchmark directory."""
    bench = root / "chipbench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    (bench / "configs" / "olmo-tiny.json").write_text(json.dumps(TINY_CONFIG))
    (bench / "traffic" / "tiny.serve.json").write_text(json.dumps(TINY_SERVE))
    (bench / "traffic" / "tiny.train.json").write_text(json.dumps(TINY_TRAIN))
    (bench / "limits" / "tiny.serve.json").write_text(json.dumps(
        serve_limits or {"max_logit_gap": {"limit": 0.1},
                         "wrong_lengths": {"limit": 0}}))
    (bench / "limits" / "tiny.train.json").write_text(json.dumps(
        train_limits or {"loss_gap": {"limit": 0.05},
                         "grad_norm_gap": {"limit": 0.05},
                         "grad_sample_gap": {"limit": 0.1},
                         "update_norm_gap": {"limit": 0.1}}))
    (bench / "metrics" / "tiny.attempted.py").write_text(TINY_METRIC)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "olmo-tiny", "source": "test",
                            "file": "chipbench/configs/olmo-tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"] += [
        {"name": "tiny.serve", "config": "olmo-tiny", "traffic": "tiny.serve",
         "chips": 1, "why": "test"},
        {"name": "tiny.train", "config": "olmo-tiny", "traffic": "tiny.train",
         "chips": 1, "why": "test"}]
    for m in spec["end_to_end"]:
        if m["name"] == "tpot_p95_ms":
            m["workloads"].append("tiny.serve")
    spec["end_to_end"].append({
        "name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher",
        "bound": 0.01, "source": "host_clock", "workloads": ["tiny.train"]})
    spec["per_layer"].append({
        "name": "tiny.attempted", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "serving engine",
        "moves": "tpot_p95_ms", "workloads": ["tiny.serve"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return bench


def cpu_look(chips, peaks):
    """Stands in for the harness's look for a chip: the CPU, with the
    v5e's peaks so that the readers have numbers to divide by."""
    import jax
    devices = jax.devices()
    return (devices[:chips], {"platform": "cpu", "kind": "cpu", "count": 1},
            peaks["TPU v5 lite"])


def no_cache():
    pass


@pytest.fixture
def tiny(tmp_path):
    return add_tiny(tmp_path)
