"""The benchmark's readers of the program's spans, counters and scopes
(``chipbench/scopes.py`` and the metrics that use it): the ``tf_op`` scope
of each operation on the chat trace recorded on one TPU v5e chip, the
existing trace readers' values on that trace, and the new readers on
hand-built runs."""
import gzip
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import bench, scopes, trace  # noqa: E402
from repro import obs  # noqa: E402

FIXTURE = ROOT / "chipbench" / "tests" / "data" / "chat.xplane.pb.gz"
# a --trace 1 run of olmo-1b.serve.chat with half a second of window, on one
# TPU v5e chip, by the program with its spans and named scopes
SCOPED = ROOT / "tests" / "data" / "chat_scoped.xplane.pb.gz"
PROGRAM_SPANS = {"engine.step", "engine.admit", "engine.prefill",
                 "engine.prefill.sync", "engine.decode", "engine.decode.sync",
                 "engine.emit"}
HARNESS_SPANS = {"engine.step", "engine.admit", "engine.prefill",
                 "engine.decode", "loadgen.wait"}


def reader(name):
    return bench.load_module(ROOT / "chipbench" / "metrics" / f"{name}.py")


def _unpack(tmp_path_factory, gz):
    path = tmp_path_factory.mktemp("xplane") / gz.name[:-3]
    path.write_bytes(gzip.decompress(gz.read_bytes()))
    return str(path)


@pytest.fixture(scope="module")
def chat_xplane(tmp_path_factory):
    return _unpack(tmp_path_factory, FIXTURE)


@pytest.fixture(scope="module")
def scoped_xplane(tmp_path_factory):
    return _unpack(tmp_path_factory, SCOPED)


def test_tf_op_scopes_on_the_recorded_chat_trace(chat_xplane):
    sc = scopes.xplane_scopes(chat_xplane)
    assert sc and all(v.startswith("jit(_decode_impl)/") for v in sc.values())
    slices = {k: v for k, v in sc.items()
              if k.startswith("%dynamic-slice_bitcast_fusion.")}
    assert len(slices) == 2
    assert set(slices.values()) == {"jit(_decode_impl)/while/body/squeeze"}
    assert sc["%fusion.147 = bf16[16,16,128]"].startswith(
        "jit(_decode_impl)/while/body/closed_call/")
    writes = [v for k, v in sc.items()
              if k.startswith("%bitcast_dynamic-update-slice_fusion.")]
    assert writes == ["jit(_decode_impl)/while/body/dynamic_update_slice"] * 2
    prefill = scopes.xplane_scopes(chat_xplane, "_prefill_impl")
    assert prefill and all(v.startswith("jit(_prefill_impl)/")
                           for v in prefill.values())


def test_existing_trace_readers_keep_their_values(chat_xplane):
    """What the accepted trace readers give on the recorded trace."""
    r = trace.read(chat_xplane, HARNESS_SPANS)
    run = types.SimpleNamespace(reduced=r)
    assert reader("model.decode_step_ms").read(run) == pytest.approx(
        35.98496, abs=1e-4)
    idle = reader("device_idle_share.online").read(run)
    assert idle == reader("device_idle_share.offline").read(run)
    assert idle == pytest.approx(100 * (1 - r.busy_s() / r.window_s))
    assert 0 < idle < 30
    top = r.top_ops(4)
    assert {n for n, _ in top[:2]} == {
        "_decode_impl/%dynamic-slice_bitcast_fusion.5 = bf16[16,2048,16,128]",
        "_decode_impl/%dynamic-slice_bitcast_fusion.4 = bf16[16,2048,16,128]"}
    assert {n for n, _ in r.idle_gaps(10)} <= HARNESS_SPANS | {
        "host outside any span"}


def test_scope_metrics_on_a_trace_with_scopes(scoped_xplane):
    """The decode step's operations carry the program's scopes in the
    trace itself, and the scope readers give the step's layers from it."""
    sc = scopes.xplane_scopes(scoped_xplane)
    assert sc and all(v.startswith("jit(_decode_impl)/decode/")
                      for v in sc.values())
    r = trace.read(scoped_xplane, PROGRAM_SPANS)
    run = types.SimpleNamespace(reduced=r, stats={"decode_scopes": sc})
    assert scopes.decode_ms(run, lambda p: True, sc) == pytest.approx(
        35.98, abs=0.01)
    assert reader("model.decode_cache_io_ms").read(run) == pytest.approx(
        26.68, abs=0.01)
    assert reader("model.decode_attention_ms").read(run) == pytest.approx(
        6.72, abs=0.01)
    assert scopes.decode_ms(run, lambda p: "mlp" in p, sc) == pytest.approx(
        2.18, abs=0.01)


def test_program_spans_on_the_scoped_trace(scoped_xplane):
    """The program's spans are on the trace's host line, each wait and
    dispatch inside an engine step."""
    r = trace.read(scoped_xplane, PROGRAM_SPANS)
    names = {n for n, _, _ in r.spans}
    assert names >= PROGRAM_SPANS
    steps = [(s, e) for n, s, e in r.spans if n == "engine.step"]
    for n, s, e in r.spans:
        if n in ("engine.decode.sync", "engine.emit", "engine.prefill.sync"):
            assert any(s0 <= s and e <= e1 for s0, e1 in steps), n


# -- the engine's spans, on hand-built runs --------------------------------------

def _span(tr, name, start, end, parent=None, counts=None):
    s = obs.Span(len(tr.spans), name, start, end, parent, None, counts)
    tr.spans.append(s)
    return s


@pytest.fixture
def steps(monkeypatch):
    """Two engine steps in a window of [10, 20] and one after it."""
    tr = obs.Tracer()
    tr.close()
    monkeypatch.setattr(obs, "_DEFAULT", tr)
    a = _span(tr, "engine.step", 10.0, 10.010,
              counts={"slots": 4, "queued": 1, "prefills": 1})
    adm = _span(tr, "engine.admit", 10.0, 10.003, a.id)
    _span(tr, "engine.prefill.sync", 10.001, 10.002, adm.id)
    _span(tr, "engine.decode.sync", 10.003, 10.009, a.id)
    b = _span(tr, "engine.step", 11.0, 11.008,
              counts={"slots": 6, "queued": 0, "prefills": 0})
    _span(tr, "engine.decode.sync", 11.0, 11.007, b.id)
    c = _span(tr, "engine.step", 30.0, 30.1,
              counts={"slots": 9, "queued": 0, "prefills": 3})
    _span(tr, "engine.decode.sync", 30.0, 30.01, c.id)
    return types.SimpleNamespace(window=(10.0, 20.0))


@pytest.mark.parametrize("name", ["engine.host_ms_per_step.online",
                                  "engine.host_ms_per_step.offline"])
def test_host_ms_per_step(steps, name):
    # (10 - 1 - 6) ms and (8 - 7) ms
    assert reader(name).read(steps) == pytest.approx(2.0)


def test_admission_gap_share(steps):
    assert reader("engine.admission_gap_share").read(steps) == \
        pytest.approx(40.0)


@pytest.mark.parametrize("name", ["engine.host_ms_per_step.online",
                                  "engine.host_ms_per_step.offline",
                                  "engine.admission_gap_share"])
def test_engine_readers_without_the_tracer(monkeypatch, name):
    """A program without ``repro.obs`` (or with no step in the window)
    gives nothing to read."""
    run = types.SimpleNamespace(window=(10.0, 20.0))
    empty = obs.Tracer()
    empty.close()
    monkeypatch.setattr(obs, "_DEFAULT", empty)
    assert reader(name).read(run) is None
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert reader(name).read(run) is None


# -- device time by scope, on hand-built runs ----------------------------------------

SCOPES = {
    "%slice = bf16[8]": "jit(_decode_impl)/decode/layers/while/body/squeeze",
    "%put = bf16[8]": "jit(_decode_impl)/decode/layers/while/body/"
                      "dynamic_update_slice",
    "%qk = f32[8]": "jit(_decode_impl)/decode/layers/while/body/closed_call/"
                    "block/attention/dot_general",
    "%mlp = bf16[8]": "jit(_decode_impl)/decode/layers/while/body/"
                      "closed_call/block/mlp/dot_general",
    "%while.1 = (bf16[8]": "jit(_decode_impl)/decode/layers/while",
}


def _device_run(scope_map, ops=None):
    """Two decode steps of 10 ms (ns on the trace's clock) and a prefill
    whose op shares a name with one of decode's."""
    ms = 1_000_000
    ops = ops or [("%while.1 = (bf16[8]", 0, 9 * ms),
                  ("%slice = bf16[8]", 0, 4 * ms),
                  ("%qk = f32[8]", 4 * ms, 6 * ms),
                  ("%mlp = bf16[8]", 6 * ms, 7 * ms),
                  ("%put = bf16[8]", 7 * ms, 9 * ms),
                  ("%slice = bf16[8]", 10 * ms, 14 * ms),
                  ("%qk = f32[8]", 14 * ms, 17 * ms),
                  ("%put = bf16[8]", 17 * ms, 19 * ms),
                  ("%slice = bf16[8]", 20 * ms, 25 * ms)]
    mods = [("_decode_impl", 0, 10 * ms), ("_decode_impl", 10 * ms, 20 * ms),
            ("_prefill_impl", 20 * ms, 25 * ms)]
    dev = trace.Device(trace.union((s, e) for _, s, e in ops), mods, ops)
    red = trace.Reduced([dev], [], (0, 30 * ms))
    return types.SimpleNamespace(reduced=red,
                                 stats={"decode_scopes": scope_map})


def test_decode_cache_io_and_attention():
    run = _device_run(SCOPES)
    # (4 + 2) + (4 + 2) ms of the scan's own slicing over two steps; the
    # loop op and the prefill's op are left out
    assert reader("model.decode_cache_io_ms").read(run) == pytest.approx(6.0)
    assert reader("model.decode_attention_ms").read(run) == \
        pytest.approx(2.5)


def test_decode_scope_readers_return_none():
    for name in ("model.decode_cache_io_ms", "model.decode_attention_ms"):
        r = reader(name)
        # no trace
        assert r.read(types.SimpleNamespace(reduced=None, stats={})) is None
        # a program without scopes: nothing in ``layers`` or ``attention``
        plain = {k: v.replace("decode/layers/", "").replace("block/", "")
                 .replace("attention/", "") for k, v in SCOPES.items()}
        assert r.read(_device_run(plain)) is None
        # a map of another program: most of the decode's time unnamed
        other = {k: v for k, v in SCOPES.items() if k != "%slice = bf16[8]"}
        assert r.read(_device_run(other)) is None


def test_hlo_scopes_inherit_the_callers_name():
    text = """HloModule jit__decode_impl

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %copy.1 = f32[4]{0} copy(%x)
  ROOT %t = (s32[], f32[4]{0}) tuple(%a, %copy.1), metadata={op_name="jit(f)/layers/while/body/tuple"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %fusion.3 = f32[4]{0:T(128)} fusion(%x), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/block/attention/mul" source_file="a.py"}
  %while.2 = (s32[], f32[4]{0}) while(%i), condition=%cond, body=%body, metadata={op_name="jit(f)/layers/while"}
  ROOT %r = f32[4]{0} get-tuple-element(%while.2), index=1
}
"""
    sc = scopes.hlo_scopes(text)
    assert sc["%fusion.3 = f32[4]"] == "jit(f)/block/attention/mul"
    assert sc["%copy.1 = f32[4]"] == "jit(f)/layers/while"
    assert sc["%while.2 = (s32[], f32[4]"] == "jit(f)/layers/while"
    assert sc["%p = (s32[], f32[4]"] == "jit(f)/layers/while"
    assert "%r = f32[4]" not in sc        # the entry has no caller
