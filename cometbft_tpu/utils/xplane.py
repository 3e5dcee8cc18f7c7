"""Reader for the profiler's trace file (`*.xplane.pb`, an XSpace
protobuf), cut to what utils/traceview.device_join needs.

jax.profiler.ProfileData reads the same file but hides the statistics
kept on an event's METADATA, and that is where XLA puts an operation's
`op_name` (the stat `tf_op`: "jit(f)/ladder.double_scalar/while/body/..."),
the path that carries the jax.named_scope and pallas_call names of
trace.KERNEL_SCOPES. The generated `xplane_pb2` would read it, but jax
ships none: the only copy here is inside the tensorflow package
(`tensorflow.tsl.profiler.protobuf`), which this program does not
depend on, an operator's node need not have, and whose import takes
seconds and its own logging. So this module decodes the protobuf wire
format itself (the six messages of xplane.proto, by field number): no
jax, no protobuf package, any machine that has the file. What guards
the field numbers against a change in jax or tsl is
tests/test_traceview.py on tests/data/v5e_probe.xplane.pb, a trace this
jax wrote on a v5e. benchmark/harness/profile.load_xplane reads the same
file through ProfileData for the ledger's breakdown; when a `benchmark`
issue carries op_name into that breakdown, the harness should call
load() here and drop its own reader (PERF.md section 7).

load(path) returns
  {"start_ns": wall-clock ns at which the session began (or None),
   "planes": [{"name": "/device:TPU:0",
               "ops":     [{"op", "op_name", "start_ns", "dur_ns"}, ...],
               "modules": [{"op", "start_ns", "dur_ns"}, ...]},
              {"name": "/host:CPU",
               "spans":   [{"name", "span_id", "line", "start_ns",
                            "dur_ns"}]}]}
with start_ns relative to the session's begin, one clock for all planes.
Device planes keep their "XLA Ops" and "XLA Modules" lines; host planes
keep only events that carry a `span_id` (the program's trace.span()s,
entered as jax.profiler.TraceAnnotation(name, span_id=id)), each with
the `line` it lay on (the line's id, its name where it has none): a host
plane has one line a thread, so a reader tells the threads apart without
the span sink. The id is the profiler's own and not the native thread id;
the sink's `tid` and `trace.thread` name a line's thread, joined by
`span_id`.
"""

from __future__ import annotations

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of each field of one message;
    a length-delimited value is a memoryview, a varint an int, fixed
    widths are skipped (nothing read here is stored in one)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield key >> 3, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf) -> tuple[int, memoryview | None]:
    key, value = 0, None
    for no, _w, v in _fields(buf):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _stat(buf, stat_names: dict) -> tuple[str | None, object]:
    """(name, value) of one XStat; a ref_value is the NAME of the stat
    metadata it points at."""
    name = value = None
    for no, wire, v in _fields(buf):
        if no == 1:
            name = stat_names.get(v)
        elif no in (3, 4) and wire == 0:
            value = v - (1 << 64) if no == 4 and v >> 63 else v
        elif no == 5:
            value = _text(v)
        elif no == 7 and wire == 0:
            value = stat_names.get(v)
    return name, value


def _plane(buf) -> dict | None:
    name = ""
    lines, event_md, stat_md, plane_stats = [], [], [], []
    for no, _w, v in _fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 3:
            lines.append(v)
        elif no == 4:
            event_md.append(v)
        elif no == 5:
            stat_md.append(v)
        elif no == 6:
            plane_stats.append(v)
    stat_names = {}
    for entry in stat_md:
        key, md = _map_entry(entry)
        for no, _w, v in _fields(md):
            if no == 2:
                stat_names[key] = _text(v)
    if name == "Task Environment":
        for st in plane_stats:
            sname, value = _stat(st, stat_names)
            if sname == "profile_start_time":
                return {"name": name, "start_ns": value}
        return None
    is_dev = name.startswith("/device:")
    if not is_dev and not name.startswith("/host:"):
        return None
    # event metadata: id -> (short name, op_name)
    md_of: dict[int, tuple[str, str]] = {}
    for entry in event_md:
        key, md = _map_entry(entry)
        full = display = op_name = ""
        for no, _w, v in _fields(md):
            if no == 2:
                full = _text(v)
            elif no == 4:
                display = _text(v)
            elif no == 5 and is_dev:
                sname, value = _stat(v, stat_names)
                if sname == "tf_op" and value:
                    op_name = str(value).rstrip(":")
        # the full name of a device operation is its whole HLO text
        short = display or full.split(" = ", 1)[0].lstrip("%")
        md_of[key] = (short, op_name)
    has_span_ids = "span_id" in stat_names.values()
    out: dict = {"name": name}
    for ln in lines:
        lname, line_id, t_line, events = "", 0, 0, []
        for no, wire, v in _fields(ln):
            if no == 1 and wire == 0:
                line_id = v
            elif no == 2:
                lname = _text(v)
            elif no == 3:
                t_line = v
            elif no == 4:
                events.append(v)
        if is_dev and lname not in (OPS_LINE, MODULES_LINE):
            continue
        if not is_dev and not has_span_ids:
            continue
        kept = []
        for ev in events:
            mid = off_ps = dur_ps = 0
            stats = []
            for no, _w, v in _fields(ev):
                if no == 1:
                    mid = v
                elif no == 2:
                    off_ps = v
                elif no == 3:
                    dur_ps = v
                elif no == 4 and not is_dev:
                    stats.append(v)  # a program span's span_id is here
            short, op_name = md_of.get(mid, ("", ""))
            rec = {"start_ns": t_line + off_ps / 1e3, "dur_ns": dur_ps / 1e3}
            if is_dev:
                rec["op"] = short
                if lname == OPS_LINE:
                    rec["op_name"] = op_name
            else:
                span_id = None
                for st in stats:
                    sname, value = _stat(st, stat_names)
                    if sname == "span_id":
                        span_id = value
                if span_id is None:
                    continue
                rec.update(name=short, span_id=span_id,
                           line=line_id or lname)
            kept.append(rec)
        key = ("spans" if not is_dev else
               "ops" if lname == OPS_LINE else "modules")
        out.setdefault(key, []).extend(kept)
    return out if len(out) > 1 else None


def load(path: str) -> dict:
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    start_ns, planes = None, []
    for no, _w, v in _fields(buf):
        if no != 1:
            continue
        plane = _plane(v)
        if plane is None:
            continue
        if "start_ns" in plane:
            start_ns = plane["start_ns"]
        else:
            planes.append(plane)
    return {"start_ns": start_ns, "planes": planes}
