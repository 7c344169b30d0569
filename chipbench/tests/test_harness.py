"""The harness end to end on the CPU at a tiny size, past its look for a
chip: a configuration, a traffic mix and a metric added as new files are
found by name; a run prints its result and its checks; faults planted in
the timed path make ``correct`` false; and without a chip, or without the
program, it prints no result and exits non-zero."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from chipbench import bench
from conftest import BENCH, REPO, cpu_look, no_cache

ARGS = ["--seed", str(2**33 + 3), "--seconds", "2"]


def run_cell(bench_dir, workload, capsys, trace=0):
    run_py = bench.load_module(bench_dir / "run.py")
    rc = run_py.main(["--workload", workload, *ARGS, "--trace", str(trace)],
                     look=cpu_look, cache=no_cache)
    cap = capsys.readouterr()
    assert rc == 0
    out = json.loads(cap.out.strip().splitlines()[-1])
    return out, cap.err.strip().splitlines()


def test_new_files_alone_add_a_cell(tiny, capsys):
    out, err = run_cell(tiny, "tiny.serve", capsys)
    assert out["correct"] is True, out
    assert out["attempted"] == 12 and out["failed"] == 0
    assert set(out["metrics"]) == {"tpot_p95_ms", "setup_s"}
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"max_logit_gap", "wrong_lengths"}
    assert err[-2].startswith("check max_logit_gap")
    assert err[-1].startswith("check wrong_lengths")


def test_traced_run_reports_the_new_metric(tiny, capsys):
    out, _ = run_cell(tiny, "tiny.serve", capsys, trace=1)
    assert out["correct"] is True, out
    assert out["metrics"] == {"tiny.attempted": {"value": 12.0,
                                                 "unit": "count"}}
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_train_cell(tiny, capsys):
    out, _ = run_cell(tiny, "tiny.train", capsys)
    assert out["correct"] is True, out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["checks"]) == {"loss_gap", "grad_norm_gap",
                                  "grad_sample_gap", "update_norm_gap"}


def test_token_altered_where_produced(tiny, capsys, monkeypatch):
    from repro.serve.engine import SlotServer
    decode = SlotServer._decode_impl

    def altered(self, *a):
        nxt, logits, caches = decode(self, *a)
        return nxt.at[0].set((nxt[0] + 1) % self.cfg.vocab_size), logits, \
            caches
    monkeypatch.setattr(SlotServer, "_decode_impl", altered)
    out, _ = run_cell(tiny, "tiny.serve", capsys)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > \
        out["checks"]["max_logit_gap"]["limit"]


def _patch_step(monkeypatch, change):
    from repro.train import step as step_mod
    make = step_mod.make_train_step

    def patched(cfg, tc):
        init_state, train_step = make(cfg, tc)
        return init_state, change(train_step)
    monkeypatch.setattr(step_mod, "make_train_step", patched)


def test_step_returns_state_unchanged(tiny, capsys, monkeypatch):
    def unchanged(train_step):
        def step(state, batch):
            _, metrics = train_step(state, batch)
            return jax.tree.map(jnp.copy, state), metrics
        return step
    _patch_step(monkeypatch, unchanged)
    out, _ = run_cell(tiny, "tiny.train", capsys)
    assert out["correct"] is False
    assert out["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(tiny, capsys, monkeypatch):
    def half(train_step):
        def step(state, batch):
            n = batch["tokens"].shape[0] // 2
            return train_step(state, {k: v[:n] for k, v in batch.items()})
        return step
    _patch_step(monkeypatch, half)
    out, _ = run_cell(tiny, "tiny.train", capsys)
    assert out["correct"] is False, out["checks"]


def test_no_chip_no_result(capsys):
    run_py = bench.load_module(BENCH / "run.py")
    rc = run_py.main(["--workload", "olmo-1b.serve.chat", *ARGS])
    cap = capsys.readouterr()
    assert rc != 0 and cap.out == ""
    assert "TPU" in cap.err


def test_unknown_device_kind(monkeypatch):
    class Dev:
        platform, device_kind = "tpu", "TPU v99"
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    run_py = bench.load_module(BENCH / "run.py")
    peaks = json.loads((BENCH / "peaks.json").read_text())
    with pytest.raises(run_py.NoChip, match="not in peaks.json"):
        run_py.look_for_chips(1, peaks)


def test_benchmark_files_alone_exit_non_zero(tmp_path):
    """A checkout of only BENCHMARK.json and the benchmark's own files
    (no program) prints no result."""
    import shutil
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "olmo-1b.serve.chat", *ARGS], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
