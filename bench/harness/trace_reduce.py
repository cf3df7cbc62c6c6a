"""From a profiler trace to numbers: device busy and idle time, time per
operation and per XLA module, and the idle gaps by what the host was doing.

Two stages, so that the arithmetic can be tested on a small recorded trace:
``read_xplane`` turns an ``.xplane.pb`` into plain lists (needs jax, nothing
else), ``reduce_events`` does the rest on those lists (stdlib only).

A document is ``{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, duration_ns], ...]}]}]}``. Device planes are ``/device:TPU:<n>``;
their line ``XLA Ops`` holds one event per executed operation, ``XLA Modules``
one per executed program. Host planes hold one line per thread, where
``jax.profiler.StepTraceAnnotation`` / ``TraceAnnotation`` spans appear by name."""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


HLO_NAME = re.compile(r"^%(\S+) = ")


def short_name(name):
    """The profiler names a device operation by its whole HLO line
    (``%flash_attention_fwd.15 = (bf16[...]) custom-call(...)``): keep the
    instruction's name."""
    m = HLO_NAME.match(name)
    return m.group(1) if m else name


def read_xplane(path, keep_host=("engine_step", "bench_step")):
    """Device lines whole (operation names shortened); of the host lines only the
    annotation spans whose name starts with one of ``keep_host``. ``extent_ns``
    is the span from the first to the last event of any plane: the traced
    window without the profiler's own start and stop."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes, lo, hi = [], None, None
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                s, d = int(e.start_ns), int(e.duration_ns)
                lo, hi = (s if lo is None else min(lo, s)), (s + d if hi is None else max(hi, s + d))
                if device and line.name in (OPS_LINE, MODULES_LINE, "Steps"):
                    events.append([short_name(e.name), s, d])
                elif not device and e.name.startswith(keep_host):
                    events.append([e.name, s, d])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "extent_ns": [lo, hi]}


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """(name, own ns) per event: an operation that encloses others (a ``while``
    round its body) keeps only the time its children do not cover."""
    out, stack = [], []  # stack of [name, end, own]
    for n, s, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - s)
        stack.append([n, s + dur, dur])
    out += [(n, own) for n, _, own in stack]
    return out


def module_base(name):
    """``jit__decode_impl(1234567)`` -> ``jit__decode_impl``."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce_events(doc, window_ns=None):
    """The window is ``window_ns`` if given, else the document's ``extent_ns``,
    else the span from the first to the last device operation."""
    devices, host_spans = [], []
    for plane in doc["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        lines = {l["name"]: l["events"] for l in plane["lines"]}
        if m:
            devices.append({"id": int(m.group(1)), "ops": lines.get(OPS_LINE, []),
                            "modules": lines.get(MODULES_LINE, [])})
        else:
            for events in lines.values():
                host_spans += [(n, s, s + d) for n, s, d in events]
    if not devices or not any(d["ops"] for d in devices):
        raise ValueError("the trace holds no device operation")
    devices.sort(key=lambda d: d["id"])
    lo = min(s for d in devices for _, s, _ in d["ops"])
    hi = max(s + dur for d in devices for _, s, dur in d["ops"])
    extent = doc.get("extent_ns")
    window = window_ns or (extent[1] - extent[0] if extent else hi - lo)

    busy = [sum(e - s for s, e in _union([(s, s + dur) for _, s, dur in d["ops"]])) for d in devices]

    first = devices[0]
    op_time, op_count, module_runs = {}, {}, {}
    for n, dur in _self_times(first["ops"]):
        op_time[n] = op_time.get(n, 0) + dur
        op_count[n] = op_count.get(n, 0) + 1
    for n, _, dur in first["modules"]:
        module_runs.setdefault(module_base(n), []).append(dur)

    merged = _union([(s, s + dur) for _, s, dur in first["ops"]])
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:]) if b[0] > a[1]]
    steps = _union([(s, e) for n, s, e in host_spans])
    classes = {}
    for s, e in gaps:
        mid = (s + e) // 2
        inside = any(a <= mid < b for a, b in steps)
        key = "inside a step annotation (host scheduling)" if inside else "between step annotations (loop, streaming, feed)"
        classes[key] = classes.get(key, 0) + (e - s)
    return {
        "window_s": window / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "busy_s_by_device": [b / 1e9 for b in busy],
        "op_seconds": {n: t / 1e9 for n, t in op_time.items()},
        "op_counts": op_count,
        "module_runs_s": {n: [x / 1e9 for x in v] for n, v in module_runs.items()},
        "idle_gap_seconds": {k: v / 1e9 for k, v in classes.items()},
        "longest_gaps_s": sorted(((e - s) / 1e9 for s, e in gaps), reverse=True)[:10],
        "host_step_spans": len(steps),
    }


def breakdown(reduced, top=10):
    """The contract's ``breakdown``: at most ``top`` entries a list."""
    ops = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(reduced["idle_gap_seconds"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops], "idle_gaps": [[n, t] for n, t in gaps]}


def kernel_seconds(reduced, names):
    """(device seconds, executions) of the operations whose name starts with one of ``names``."""
    names = tuple(names)
    hit = [n for n in reduced["op_seconds"] if n.startswith(names)]
    return sum(reduced["op_seconds"][n] for n in hit), sum(reduced["op_counts"][n] for n in hit)


def idle_share(run):
    """Percent of the traced span in which no operation ran on the device, mean over the chips."""
    trace = run.get("trace")
    if not trace:
        return None
    return (1.0 - trace["busy_s"] / trace["window_s"]) * 100.0


def roofline_share(run, kernel_file, kernels):
    """Percent: the least time the chip could take for the calls of ``kernels`` seen in the
    trace (``bench/kernels/<kernel_file>.py`` at the step's static shapes) over their device time."""
    from . import loader

    trace = run.get("trace")
    if not trace:
        return None
    k = loader.module_from("kernels", kernel_file)
    shape = k.shape_of(run["config"], run["rows_per_chip"], run["seq_len"])
    total = least = 0.0
    for name in kernels:
        seconds, calls = kernel_seconds(trace, (name,))
        total += seconds
        least += calls * k.least_seconds(name, shape, run["peaks"])
    return least / total * 100.0 if total else None
