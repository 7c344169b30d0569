"""BENCHMARK.json and the files it names agree: every metric has its
reader, every per-layer metric's cells report the end-to-end metric it
moves, every cell reports set-up, another end-to-end metric and a
per-layer metric, and each configuration file is the configuration it
names, its LayerNorm epsilon the reference's."""
import dataclasses
import json
import re

import pytest

from chipbench import bench
from chipbench.weights import arch_config, load_config
from conftest import BENCH, REPO

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [c["name"] for c in SPEC["workloads"]]


def test_names_and_files():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in SPEC[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for c in SPEC["workloads"]:
        assert (BENCH / "traffic" / f"{c['traffic']}.json").exists()
        assert (BENCH / "limits" / f"{c['name']}.json").exists()
        assert c["chips"] == 1
    for c in SPEC["configs"]:
        assert (REPO / c["file"]).exists()


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_and_its_cells_report_what_it_moves(m):
    assert (BENCH / "metrics" / f"{m['name']}.py").exists()
    moved = next(e for e in SPEC["end_to_end"] if e["name"] == m["moves"])
    for cell in m["workloads"]:
        assert cell in moved.get("workloads", CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_enough(cell):
    e2e = [m["name"] for m in bench.cell_metrics(SPEC, cell, False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert bench.cell_metrics(SPEC, cell, True)
    for m in e2e:
        assert (BENCH / "metrics" / f"{m}.py").exists()


def test_olmo_1b_is_the_registry_entry():
    from repro.configs.registry import get_config
    assert arch_config(load_config("olmo-1b")) == get_config("olmo-1b")


def test_olmo_7b_is_olmo_1b_code_at_7b_widths():
    a1 = arch_config(load_config("olmo-1b"))
    a7 = arch_config(load_config("olmo-7b"))
    cfg = load_config("olmo-7b")
    same = dataclasses.replace(a7, name=a1.name, n_layers=a1.n_layers,
                               d_model=a1.d_model, n_heads=a1.n_heads,
                               n_kv_heads=a1.n_kv_heads, d_ff=a1.d_ff,
                               tie_embeddings=a1.tie_embeddings)
    assert same == a1
    assert cfg["reduced"] == ["n_layers"] and cfg["published"]["n_layers"] == 32
    assert (a7.d_model, a7.n_heads, a7.d_ff * 2) == \
        (cfg["d_model"], cfg["n_heads"], cfg["mlp_hidden_size"])
    assert a7.n_layers == cfg["n_layers"] == 8


@pytest.mark.parametrize("name", ["olmo-1b", "olmo-7b"])
def test_reference_uses_the_configured_epsilon(name):
    ref = bench.load_module(BENCH / "references" / "olmo.py")
    assert ref.LN_EPS == load_config(name)["layer_norm_eps"] == 1e-5
