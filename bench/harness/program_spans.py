"""What the program's own tracing says about a traced serving run: the device's
idle time by the host phase it fell into, and the decode program's device time
by the ``jax.named_scope`` of its operations.

Two records are read and laid on one clock.

(i) The profiler's ``.xplane.pb`` at ``run["tracer"].xplane_path()``, by a
stdlib reader of its wire format (``read_xplane``): jax's ``ProfileData`` shows
an event's own stats but not its metadata's, and the scope of a device
operation lives there, in the stat ``tf_op`` (found on the chip, PR 24:
``jit(_decode_impl)/while/body/closed_call/qkv/dot_general:``; an operation the
compiler inserted carries no scope, or none at all). Host annotations
(``TraceMe``) carry their args as event stats. Of the thread lines that hold an
``engine_step`` annotation (the engine loop's) the program's phases are kept;
the profiler's own Python-frame events on the same line are not.

(ii) ``TRACER.snapshot()`` of the program, in process (the server runs in the
harness's process): retrospective per-request spans (``inbox``, ``queue``,
``prefill``), ``loop_idle`` episodes and the launch spans' geometry args, which
are complete only after the launch. They are placed on the trace's clock by
the launch spans that exist in both records (the program mirrors every live
engine span to the profiler). Nothing is returned if the ring dropped a span
recorded after the measurement window or the traced span began, whichever is
the earlier: the request clock is read over the whole window.

``reduce`` does the arithmetic on plain lists, so that it can be tested on a
hand-made document (``tests/bench/test_bench_program_spans.py``). A program
without mirrored spans or scopes (the parent of PR 24) gives ``None``
everywhere: the readers then return nothing and do not raise."""

from __future__ import annotations

import bisect
import re
import statistics
import struct
import time

from .common import log
from .trace_reduce import _union, short_name

OP_NAME_STAT = "tf_op"
STEP = "engine_step"
LOOP = ("loop_intake", "loop_finish", "loop_idle")
LAUNCHES = ("prefill", "decode", "mixed_step", "spec_verify")
LAUNCH = LAUNCHES + ("dispatch", "wait")
SCHED = ("admission", "prefix_cache", "launch_build", "emit", "step_tail", "spec_propose", "sampling")
PHASES = (STEP,) + LOOP + LAUNCH + SCHED  # the program's; the profiler's own Python frames share the line
SCOPES = ("embed", "attn_norm", "qkv", "rope", "kv_write", "paged_attn", "attn_gather", "o_proj",
          "mlp_norm", "mlp", "final_norm", "lm_head", "sample", "bookkeeping")
TIMELINE = ("inbox", "queue", "prefill", "request")  # retrospective spans of one request (cat="request")
MATMUL_SCOPES = ("qkv", "o_proj", "mlp", "lm_head", "embed")
DECODE_MODULE, PAGED_KERNEL = "jit__decode_impl", "ragged_paged_attention"
DEVICE_PLANE, OPS_LINE, MODULES_LINE = "/device:TPU:0", "XLA Ops", "XLA Modules"
MODULE_ID = re.compile(r"^(.*)\((\d+)\)$")


# ---------------------------------------------------------------- the xplane, by hand
def _varint(buf, i):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one protobuf message: varints as
    ints, length-delimited fields as bytes, fixed fields as their bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield field, wire, value


def _signed(x):
    return x - (1 << 64) if x >= 1 << 63 else x


def _stat(buf, stat_names):
    """(name, value) of one XStat {1 metadata_id, 2 double, 3 uint64, 4 int64,
    5 str, 6 bytes, 7 ref to a stat name}."""
    name, value = None, None
    for field, _, v in _fields(buf):
        if field == 1:
            name = stat_names.get(v, str(v))
        elif field == 2:
            value = struct.unpack("<d", v)[0]
        elif field == 3:
            value = v
        elif field == 4:
            value = _signed(v)
        elif field == 5:
            value = v.decode("utf-8", "replace")
        elif field == 6:
            value = bytes(v)
        elif field == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf):
    key = value = None
    for field, _, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _plane(buf):
    """{name, lines: [{name, events: [[name, start_ns, dur_ns, event stats, metadata stats]]}]}
    of one XPlane {2 name, 3 lines, 4 event_metadata, 5 stat_metadata}."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for field, _, v in _fields(buf):
        if field == 2:
            name = v.decode()
        elif field == 3:
            lines.append(v)
        elif field == 4:
            key, value = _map_entry(v)
            event_meta[key] = value
        elif field == 5:
            key, value = _map_entry(v)
            stat_names[key] = next((x.decode() for f, _, x in _fields(value) if f == 2), "")
    meta = {}
    for key, value in event_meta.items():  # XEventMetadata {2 name, 5 stats}
        ev_name, stats = "", {}
        for field, _, v in _fields(value):
            if field == 2:
                ev_name = v.decode("utf-8", "replace")
            elif field == 5:
                k, x = _stat(v, stat_names)
                stats[k] = x
        meta[key] = (ev_name, stats)
    out = []
    for raw in lines:  # XLine {2 name, 3 timestamp_ns, 4 events}
        line_name, t0_ns, events = "", 0, []
        for field, _, v in _fields(raw):
            if field == 2:
                line_name = v.decode()
            elif field == 3:
                t0_ns = _signed(v)
            elif field == 4:
                events.append(v)
        rows = []
        for ev in events:  # XEvent {1 metadata_id, 2 offset_ps, 3 duration_ps, 4 stats}
            mid = offset_ps = dur_ps = 0
            stats = {}
            for field, _, v in _fields(ev):
                if field == 1:
                    mid = v
                elif field == 2:
                    offset_ps = _signed(v)
                elif field == 3:
                    dur_ps = _signed(v)
                elif field == 4:
                    k, x = _stat(v, stat_names)
                    stats[k] = x
            ev_name, meta_stats = meta.get(mid, ("", {}))
            rows.append([ev_name, t0_ns + offset_ps / 1000.0, dur_ps / 1000.0, stats, meta_stats])
        out.append({"name": line_name, "events": rows})
    return {"name": name, "lines": out}


def read_xplane(path):
    """The document ``reduce`` takes: ``ops`` ``[name, start_ns, dur_ns, op_name,
    program_id]`` and ``modules`` ``[name, start_ns, dur_ns]`` of the first chip,
    ``host`` ``[name, start_ns, dur_ns, args]`` of the engine loop's thread, and
    ``extent_ns``, the span from the first to the last event of any plane (the
    traced span, as ``trace_reduce`` takes it)."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = [_plane(v) for field, _, v in _fields(buf) if field == 1]
    lo = hi = None
    ops, modules, host = [], [], []
    for plane in planes:
        for line in plane["lines"]:
            for name, start, dur, stats, meta in line["events"]:
                lo = start if lo is None else min(lo, start)
                hi = start + dur if hi is None else max(hi, start + dur)
            if plane["name"] == DEVICE_PLANE and line["name"] == OPS_LINE:
                for name, start, dur, stats, meta in line["events"]:
                    ops.append([short_name(name), start, dur, meta.get(OP_NAME_STAT),
                                str(meta.get("program_id", ""))])
            elif plane["name"] == DEVICE_PLANE and line["name"] == MODULES_LINE:
                modules += [[name, start, dur] for name, start, dur, _, _ in line["events"]]
            elif plane["name"].startswith("/host:") and any(e[0] == STEP for e in line["events"]):
                host += [[name, start, dur, stats] for name, start, dur, stats, _ in line["events"]
                         if name in PHASES]
    return {"ops": ops, "modules": modules, "host": host, "extent_ns": [lo, hi]}


# ---------------------------------------------------------------- the arithmetic
class _Cover:
    """Length of the overlap of [a, b) with a set of disjoint sorted intervals."""

    def __init__(self, intervals):
        self.starts = [s for s, _ in intervals]
        self.ends = [e for _, e in intervals]
        self.before = [0.0]
        for s, e in intervals:
            self.before.append(self.before[-1] + (e - s))

    def _upto(self, x):
        i = bisect.bisect_right(self.starts, x)
        if i == 0:
            return 0.0
        return self.before[i - 1] + min(x, self.ends[i - 1]) - self.starts[i - 1]

    def of(self, a, b):
        return self._upto(b) - self._upto(a) if b > a else 0.0


def _own(events, cover):
    """(name, own overlap with ``cover``, inside an engine_step) per event of
    one thread: a span that encloses others keeps only what they do not cover,
    so every instant goes to the innermost phase open at it."""
    out, stack = [], []  # stack of [name, start, end, own, in_step]
    for name, s, e in sorted(events, key=lambda x: (x[1], x[1] - x[2])):
        while stack and stack[-1][2] <= s:
            out.append(stack.pop())
        if stack:
            e = min(e, stack[-1][2])
            stack[-1][3] -= cover.of(s, e)
        stack.append([name, s, e, cover.of(s, e), name == STEP or bool(stack and stack[-1][4])])
    out += stack
    return [(name, own, in_step) for name, _, _, own, in_step in out]


def _self_times(ops):
    """(index of the operation, own ns): an enclosing ``while`` keeps what its body does not cover."""
    out, stack = [], []  # [index, end, own]
    for i in sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2])):
        s, dur = ops[i][1], ops[i][2]
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - s)
        stack.append([i, s + dur, dur])
    out += [(i, own) for i, _, own in stack]
    return out


def scope_of(op_name):
    """The innermost of the program's scopes on an operation's ``op_name`` path, or None."""
    if not op_name:
        return None
    parts = op_name.rstrip(":").split("/")[:-1]
    return next((p for p in reversed(parts) if p in SCOPES), None)


def clock_offsets(doc, spans):
    """ns to add to a TRACER time (s * 1e9) to land on the trace's clock, one per
    launch span found in both records (same name, same ``step``, same order within it)."""
    def keyed(rows):
        seen, out = {}, {}
        for name, step, start in sorted(rows, key=lambda r: r[2]):
            k = (name, int(step))
            seen[k] = seen.get(k, 0) + 1
            out[k + (seen[k],)] = start
        return out

    trace = keyed([(n, a["step"], s) for n, s, _, a in doc["host"] if n in LAUNCHES and "step" in a])
    prog = keyed([(s["name"], s["args"]["step"], s["ts"] * 1e9) for s in spans
                  if s["name"] in LAUNCHES and s.get("cat") == "engine" and "step" in (s.get("args") or {})])
    return [trace[k] - prog[k] for k in trace if k in prog]


def reduce(doc, spans, ring, window_s):
    """``doc`` from ``read_xplane``; ``spans`` TRACER spans as dicts (``ts`` and
    ``dur`` in seconds on the tracer's clock); ``ring`` ``{"dropped", "kept_since"}``
    (see ``ring_state``); ``window_s`` the measurement window on the tracer's
    clock. Returns ``{"metrics", "host_phases", "device_scopes"}``, or None where
    the program's spans cannot be laid on the trace (no launch span in both
    records) or the ring dropped spans recorded inside the window or the traced
    span."""
    if not doc["ops"]:
        return None
    offsets = clock_offsets(doc, spans)
    if not offsets:
        return None
    offset = statistics.median(offsets)
    lo, hi = doc["extent_ns"]
    to_trace = lambda t_s: t_s * 1e9 + offset
    if ring["dropped"] and (ring["kept_since"] is None
                            or to_trace(ring["kept_since"]) > min(lo, to_trace(window_s[0]))):
        return None
    window = hi - lo

    merged = _union([(s, s + d) for _, s, d, _, _ in doc["ops"]])
    busy = sum(e - s for s, e in merged)
    gaps = _Cover([(a[1], b[0]) for a, b in zip(merged, merged[1:]) if b[0] > a[1]])
    idle_between_ops = gaps.before[-1]

    # ---- idle time by the innermost host phase open at it
    events = [(n, s, s + d) for n, s, d, _ in doc["host"]]
    events += [("loop_idle", to_trace(s["ts"]), to_trace(s["ts"] + s["dur"]))
               for s in spans if s["name"] == "loop_idle"]
    events = [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]
    by_phase, classes = {}, {"loop": 0.0, "sched": 0.0, "launch": 0.0, "uncovered": 0.0}
    for name, own, in_step in _own(events, gaps):
        by_phase[name] = by_phase.get(name, 0.0) + own
        kind = ("uncovered" if name == STEP else "launch" if name in LAUNCH else
                "sched" if in_step and name in SCHED else "loop")
        classes[kind] += own
    classes["uncovered"] += idle_between_ops - sum(classes.values())  # no phase open at all
    busy_cover = _Cover(merged)
    busy_in_steps = sum(busy_cover.of(s, e) for n, s, e in events if n == STEP)

    # ---- what the launches were asked to do (program spans inside the traced span)
    inside = [s for s in spans if s.get("cat") == "engine" and s["name"] in LAUNCHES
              and to_trace(s["ts"]) >= lo and to_trace(s["ts"] + s["dur"]) <= hi]
    decodes = [s for s in inside if s["name"] == "decode" and "rows" in (s.get("args") or {})]
    geometry = {k: sum(s["args"][k] for s in decodes)
                for k in ("rows_live", "rows", "kv_positions")} if decodes else None

    # ---- the decode program's device time by scope
    ids = {m.group(2) for m in (MODULE_ID.match(n) for n, _, _ in doc["modules"])
           if m and m.group(1) == DECODE_MODULE}
    by_scope, top, paged_in_launches = {}, {}, 0.0
    launch_cover = _Cover(_union([(to_trace(s["ts"]), to_trace(s["ts"] + s["dur"])) for s in decodes]))
    for i, own in _self_times(doc["ops"]):
        name, start, _, op_name, program = doc["ops"][i]
        if program not in ids:
            continue
        scope = scope_of(op_name) or "unscoped"
        by_scope[scope] = by_scope.get(scope, 0.0) + own
        top[(name, scope)] = top.get((name, scope), 0.0) + own
        if name.startswith(PAGED_KERNEL) and launch_cover.of(start, start + 1) > 0:
            paged_in_launches += own
    decode_ns = sum(by_scope.values())

    # ---- the request clock
    t0, t1 = window_s
    requests = {}
    for s in spans:
        if s.get("cat") == "request" and s["name"] in TIMELINE and s.get("trace"):
            requests.setdefault(s["trace"], {})[s["name"]] = s
    # the requests that finished inside the window: the population of the
    # program's own queue_wait histogram, which is observed at finish
    inbox_finished = [r["inbox"]["dur"] for r in requests.values() if "inbox" in r and "request" in r
                      and t0 <= r["request"]["ts"] + r["request"]["dur"] < t1]
    # the requests submitted inside the window: the client's population
    submitted = [r for r in requests.values() if all(p in r for p in TIMELINE[:3]) and t0 <= r["inbox"]["ts"] < t1]
    server_ttft = [sum(r[p]["dur"] for p in TIMELINE[:3]) for r in submitted]

    share = lambda ns: ns / window * 100.0
    part = lambda ns: ns / decode_ns * 100.0
    metrics = {
        "inbox_wait_mean_ms": statistics.fmean(inbox_finished) * 1e3 if inbox_finished else None,
        "idle_loop_share": share(classes["loop"]),
        "idle_sched_share": share(classes["sched"]),
        "idle_launch_share": share(classes["launch"]),
        "batch_occupancy": geometry["rows_live"] / geometry["rows"] * 100.0 if geometry else None,
        "decode_matmul_share": part(sum(by_scope.get(s, 0.0) for s in MATMUL_SCOPES)) if decode_ns else None,
        "decode_kv_pool_share": part(by_scope.get("kv_write", 0.0)) if decode_ns else None,
        "decode_unscoped_share": part(by_scope.get("unscoped", 0.0)) if decode_ns else None,
    }
    ms = lambda ns: round(ns / 1e6, 3)
    host_phases = {
        "window_ms": ms(window), "busy_ms": ms(busy), "idle_ms": ms(window - busy),
        "idle_ms_by_class": {k: ms(v) for k, v in classes.items()},
        "idle_ms_at_the_edges": ms(window - busy - idle_between_ops),
        "uncovered_share": share(classes["uncovered"]),
        "idle_ms_by_phase": {k: ms(v) for k, v in sorted(by_phase.items(), key=lambda kv: -kv[1])},
        "clock": {"mirrored_spans": len(offsets), "offset_spread_ms": ms(max(offsets) - min(offsets)),
                  "ring_dropped": ring["dropped"]},
        # ROADMAP S2: the step anatomy's device seconds are the launch spans' own durations
        "launch_span_ms": ms(sum(s["dur"] for s in inside) * 1e9), "launch_spans": len(inside),
        "device_busy_in_launch_spans_ms": ms(sum(busy_cover.of(to_trace(s["ts"]), to_trace(s["ts"] + s["dur"]))
                                                 for s in inside)),
        "device_busy_in_engine_steps_ms": ms(busy_in_steps),
        "decode_launches": len(decodes), "decode_geometry": geometry,
        "inbox_ms": {"n": len(inbox_finished), "mean": metrics["inbox_wait_mean_ms"],
                     "max": max(inbox_finished) * 1e3 if inbox_finished else None},
        "server_ttft_ms": {"n": len(server_ttft),
                           "mean": statistics.fmean(server_ttft) * 1e3 if server_ttft else None,
                           "phase_mean": {p: statistics.fmean(r[p]["dur"] for r in submitted) * 1e3
                                          for p in TIMELINE[:3]} if submitted else None},
    }
    device_scopes = {
        "program": DECODE_MODULE, "device_ms": ms(decode_ns),
        "ms_by_scope": {k: ms(v) for k, v in sorted(by_scope.items(), key=lambda kv: -kv[1])},
        "largest_ops": [[n, sc, ms(v)] for (n, sc), v in sorted(top.items(), key=lambda kv: -kv[1])[:16]],
        "paged_kernel_ms_in_counted_launches": ms(paged_in_launches),
    }
    return {"metrics": metrics, "host_phases": host_phases, "device_scopes": device_scopes,
            "paged_kernel_s": paged_in_launches / 1e9, "kv_positions": geometry["kv_positions"] if geometry else None}


# ---------------------------------------------------------------- the run
def ring_state(dropped, spans):
    """``kept_since``: an instant on the tracer's clock from which every span
    recorded is still in the ring (``spans``, oldest first). The ring drops in
    the order of recording, and a span of the engine or the loop is recorded as
    it ends (a request's are recorded when the request finishes, long after
    their start), so the end of the first such span bounds when the oldest
    span left was recorded. None where there is no such span."""
    first = next((s for s in spans if s.get("cat") in ("engine", "engine_loop") and "dur" in s), None)
    return {"dropped": dropped, "kept_since": first["ts"] + first["dur"] if first else None}


def tables(run):
    """``reduce`` over this run's two records, once: the result is kept in
    ``run`` and its two tables are logged. None where there is nothing to read."""
    if "program_spans" in run:
        return run["program_spans"]
    run["program_spans"] = out = None
    try:
        if run.get("kind") == "serve" and run.get("tracer") is not None:
            from paddlenlp_tpu.observability.tracer import TRACER

            spans = [s.to_dict() for s in TRACER.snapshot()]
            ring = ring_state(TRACER.dropped, spans)
            shift = TRACER.now() - time.monotonic()  # the harness stamps its scrapes on time.monotonic()
            out = reduce(read_xplane(run["tracer"].xplane_path()), spans, ring,
                         (run["before"]["t"] + shift, run["after"]["t"] + shift))
    except Exception as e:  # a reader that finds nothing returns nothing
        log(phase="program_spans", error=repr(e)[:300])
    if out is not None:
        log(phase="host_phases", **out["host_phases"])
        log(phase="device_scopes", **out["device_scopes"])
        run["program_spans"] = out
    return out


def metric(run, name):
    out = tables(run)
    return None if out is None else out["metrics"].get(name)
