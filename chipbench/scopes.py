"""Which layer of the model each device operation belongs to, and what the
serving engine's own spans say.

The program names its layers with ``jax.named_scope`` (``decode``,
``layers``, ``block``, ``attention``, ``kv_cache``, ``mlp``, ``norm``,
``embed``, ``head``).  The compiler keeps that name stack in each
operation's metadata (``op_name``), and the profiler copies it into the
trace as the operation's ``tf_op``.  Two readers of it:

* ``hlo_scopes``: from a compiled program's HLO text.  ``decode_scopes``
  compiles ``SlotServer._decode_impl`` again at the cell's shapes, which
  gives the operations the run traced under the same names, so a reader
  can name the operations of the reduced trace after the trace file is
  gone;
* ``xplane_scopes``: from the trace file's event metadata, with a small
  inline description of the trace's protobuf messages.

An operation's scope is its ``op_name`` split on ``/``; it is "in scope
``s``" when ``s`` is one of the parts.

The engine's spans and counters (``repro.obs``) are read from the
process's default tracer; a program without it has none, and the readers
then return None.
"""
from __future__ import annotations

import bisect
import re

from chipbench import trace

DECODE = "_decode_impl"
# the share of the traced decode's device time whose operations the
# compiled program must name, or the map is not of the program traced
MIN_MATCHED = 0.99
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+ = .*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=(%[\w.\-]+)")


def hlo_scopes(hlo_text: str) -> dict:
    """{operation as ``trace.op_name`` gives it: its ``op_name``} of every
    instruction of an HLO module's text.  An instruction without one takes
    that of the instruction that calls its computation (a loop's body
    takes the loop's), as the profiler's ``tf_op`` does."""
    own, home, caller = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        c = _COMPUTATION.match(line)
        if c:
            comp = c.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        key = trace.op_name(m.group(1))
        s = _OP_NAME.search(line)
        own[key], home[key] = (s.group(1) if s else None), comp
        for called in _CALLED.findall(line):
            caller[called] = key

    def scope(key, depth=0):
        if own[key] is not None or depth > 16:
            return own[key]
        up = caller.get(home[key])
        return scope(up, depth + 1) if up else None
    return {k: v for k in own if (v := scope(k)) is not None}


def decode_ms(run, keep, scopes: dict) -> float | None:
    """Device milliseconds per execution of the decode step, in the traced
    window, of the operations whose scope parts ``keep`` accepts; loops and
    calls, whose time is that of the ops inside them, are left out.  None
    without a trace or a decode, where ``scopes`` does not name the traced
    operations, or where no operation is kept."""
    r = run.reduced
    if r is None or not r.devices or not scopes:
        return None
    t0, t1 = r.window
    d = r.devices[0]
    starts = [s for _, s, _ in d.modules]
    n = sum(1 for m, s, _ in d.modules if m == DECODE and t0 <= s < t1)
    total = named = kept = 0.0
    for name, s, e in d.ops:
        if e <= t0 or s >= t1 or trace.CONTAINER_OP.match(name):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or d.modules[i][0] != DECODE or s >= d.modules[i][2]:
            continue
        dt = min(e, t1) - max(s, t0)
        total += dt
        scope = scopes.get(name)
        if scope is not None:
            named += dt
            if keep(scope.split("/")):
                kept += dt
    if not n or not total or named < MIN_MATCHED * total or not kept:
        return None
    return kept * 1e-6 / n


def decode_scopes(run) -> dict:
    """The op scopes of ``SlotServer._decode_impl`` compiled again at the
    cell's shapes (the same operations, under the same names, as the
    program traced), kept in ``run.stats``; empty where that fails."""
    if "decode_scopes" not in run.stats:
        try:
            run.stats["decode_scopes"] = hlo_scopes(_decode_hlo(run))
        except Exception as e:     # a reader returns None, never raises
            run.stats["decode_scopes"] = {}
            run.stats["decode_scopes_error"] = repr(e)
    return run.stats["decode_scopes"]


def _decode_hlo(run) -> str:
    import jax
    import jax.numpy as jnp

    from chipbench import weights
    from repro.models import transformer
    from repro.serve.engine import SlotServer

    eng = run.traffic["engine"]
    B, L = eng["max_slots"], eng["max_len"]
    params = weights._unflatten({p: {} if s is None else s for p, s in
                                 weights.layout(run.arch).items()})
    caches = jax.eval_shape(lambda: transformer.init_caches(run.arch, B, L))
    srv = SlotServer.__new__(SlotServer)     # the step needs only cfg
    srv.cfg = run.arch
    vec = jax.ShapeDtypeStruct((B,), jnp.int32)
    active = jax.ShapeDtypeStruct((B,), jnp.bool_)
    lowered = jax.jit(srv._decode_impl).lower(params, vec, vec, caches,
                                              active)
    # the compile cache's key leaves out the metadata the scopes live in,
    # so without this it could hand back a program compiled without them
    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update(key, was)


# -- the trace file's own scopes ----------------------------------------------

def _xspace_class():
    """The trace's ``XSpace`` message, described inline: only the fields
    read here (plane name, event and stat metadata, stats)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="chipbench_xplane.proto",
                                           package="chipbench.xplane",
                                           syntax="proto3")

    def msg(name, *fields, parent=None):
        m = (parent.nested_type if parent else f.message_type).add(name=name)
        for num, fname, ftype, label, tname in fields:
            fd = m.field.add(name=fname, number=num, type=ftype,
                             label=label)
            if tname:
                fd.type_name = tname
        return m

    one, rep = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64, dbl, s, b, m = (F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE,
                              F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE)
    P = ".chipbench.xplane."
    msg("XStat", (1, "metadata_id", i64, one, None),
        (2, "double_value", dbl, one, None), (3, "uint64_value", u64, one,
                                              None),
        (4, "int64_value", i64, one, None), (5, "str_value", s, one, None),
        (6, "bytes_value", b, one, None), (7, "ref_value", u64, one, None))
    msg("XEventMetadata", (1, "id", i64, one, None), (2, "name", s, one, None),
        (4, "display_name", s, one, None), (5, "stats", m, rep, P + "XStat"))
    msg("XStatMetadata", (1, "id", i64, one, None), (2, "name", s, one, None))
    plane = msg("XPlane", (1, "id", i64, one, None), (2, "name", s, one, None),
                (4, "event_metadata", m, rep, P + "XPlane.EventMetadataEntry"),
                (5, "stat_metadata", m, rep, P + "XPlane.StatMetadataEntry"))
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = msg(entry, (1, "key", i64, one, None),
                (2, "value", m, one, P + value), parent=plane)
        e.options.map_entry = True
    msg("XSpace", (1, "planes", m, rep, P + "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench.xplane.XSpace"))


def xplane_scopes(path: str, program: str = DECODE) -> dict:
    """{operation as ``trace.op_name`` gives it: its ``tf_op``} of one
    program's operations on the device planes of an ``.xplane.pb`` file."""
    space = _xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out = {}
    for plane in space.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        tf_op = [k for k, v in stat_names.items() if v == "tf_op"]
        for md in plane.event_metadata.values():
            for st in md.stats:
                if tf_op and st.metadata_id == tf_op[0]:
                    # a string may be interned as a stat metadata's name;
                    # tf_op is "<op_name>:<op type>"
                    op = (stat_names.get(st.ref_value, "") if st.ref_value
                          else st.str_value).rsplit(":", 1)[0]
                    if op.startswith(f"jit({program})/"):
                        out[trace.op_name(md.name)] = op
    return out


# -- the engine's own spans ------------------------------------------------------

def engine_steps(run) -> list:
    """``(step span, spans inside it)`` of each engine step that began in
    the window, from the program's default tracer; empty where the program
    records none."""
    try:
        from repro import obs
    except ImportError:
        return []
    spans = obs.tracer().spans
    by_parent: dict = {}
    for s in spans:
        by_parent.setdefault(s.parent, []).append(s)

    def inside(span):
        out = []
        for c in by_parent.get(span.id, []):
            out += [c] + inside(c)
        return out
    t0, t1 = run.window
    return [(s, inside(s)) for s in spans
            if s.name == "engine.step" and t0 <= s.start <= t1]


def host_ms_per_step(run) -> float | None:
    """Mean host milliseconds of an engine step less the waits on the
    device inside it (``*.sync`` spans)."""
    steps = engine_steps(run)
    if not steps:
        return None
    own = [s.seconds - sum(c.seconds for c in kids if c.name.endswith(".sync"))
           for s, kids in steps]
    return 1e3 * sum(own) / len(own)
