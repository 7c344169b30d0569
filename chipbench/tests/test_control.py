"""The control at a size a test run holds: the float32 reference computed
with float8 linear layers, in the program's place, and each fault the
cell can have come out not correct against the cell's limits, judged as
``correct`` judges the program, on every seed, while the program comes out
correct.  ``chipbench/control.py`` takes the same readings on the chip at
the cells' own sizes, where the limits are set from them."""
import argparse

import pytest

from chipbench import bench
from conftest import add_tiny, cpu_look, no_cache


# At the tiny width the serving gaps are small: on these seeds the program
# reads at most 0.0018 and the control at least 0.0047, so the tiny serving
# cell's limit lies between them.
SERVE_LIMITS = {"max_logit_gap": {"limit": 0.003},
                "wrong_lengths": {"limit": 0}}


@pytest.mark.parametrize("workload", ["tiny.serve", "tiny.train"])
def test_control_fails_a_number(tmp_path, workload, capsys):
    tiny = add_tiny(tmp_path, serve_limits=SERVE_LIMITS)
    ctl = bench.load_module(tiny / "control.py")
    args = argparse.Namespace(workload=workload, seeds=2, control_seeds=2,
                              first_seed=2**31 + 5, seconds=2.0)
    rows = ctl.readings(args, look=cpu_look, cache=no_cache)
    assert all(r["correct"] for r in rows)
    for r in rows:
        assert "control" in r["judged"], r
        assert not any(r["judged"].values()), r
    s = ctl.summary(rows)
    assert s["program_correct"] and s["controls_and_faults_fail"]
