"""The trace reduction, on a short trace recorded on one TPU v5e chip by a
``--trace 1`` run of ``olmo-1b.serve.chat`` (half a second of window),
and on hand-made intervals."""
import gzip
from pathlib import Path

import pytest

from chipbench import trace

FIXTURE = Path(__file__).parent / "data" / "chat.xplane.pb.gz"
SPANS = {"engine.step", "engine.admit", "engine.prefill", "engine.decode",
         "loadgen.wait"}


def test_union_and_clip():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_names():
    assert trace.module_name("jit__decode_impl(15026748185489725708)") == \
        "_decode_impl"
    assert trace.op_name("%fusion.140 = s32[16]{0:T(128)S(1)} fusion(s32[16]"
                         "{0:T(128)} %x), kind=kLoop") == "%fusion.140 = s32[16]"


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("xplane") / "chat.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    return trace.read(str(path), SPANS)


def test_recorded_trace(reduced):
    assert len(reduced.devices) == 1
    assert 0.4 < reduced.window_s < 1.0            # the bench.window span
    busy = reduced.busy_s()
    assert 0 < busy <= reduced.window_s
    decodes = reduced.module_times(r"^_decode_impl$")
    assert decodes and all(0.01 < t < 0.1 for t in decodes)
    assert sum(decodes) <= busy


def test_breakdown(reduced):
    ops = reduced.top_ops(10)
    assert 0 < len(ops) <= 10
    assert all(isinstance(n, str) and v > 0 for n, v in ops)
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    assert any(n.startswith("_decode_impl/") for n, _ in ops)
    assert not any("/%while" in n for n, _ in ops)
    gaps = reduced.idle_gaps(10)
    assert 0 < len(gaps) <= 10
    idle = reduced.window_s - reduced.busy_s()
    assert sum(v for _, v in gaps) == pytest.approx(idle, rel=1e-6, abs=1e-9)
    assert {n for n, _ in gaps} <= SPANS | {"host outside any span"}
