"""Offline postmortem-bundle analyzer: per-request cross-tier timelines.

Reads one or more postmortem bundles (auto-dumped by a supervisor degrade /
slot quarantine / drain eviction / SLO fast burn, or forced via
``POST /debug/postmortem``) and reconstructs what happened:

- ``--list`` enumerates every request (trace id) seen in the bundles' flight
  events, with event counts per tier;
- ``--req rtr-3`` (or ``req-0``, or a bare engine req_id) prints that
  request's **decision trail** — router-tier and replica-tier flight events
  joined on the shared trace id, merged with the request's spans into one
  monotonic timeline — plus its **latency-attribution breakdown** from the
  bundle's finished-request tail;
- with no selector, a bundle summary (trigger, tier, health headlines,
  event/span counts) is printed.

Bundles from one process (an in-process fleet) already carry both tiers;
separate router/replica processes each dump their own bundle — pass all of
them and the analyzer merges on the trace id. Timestamps inside one process
are epoch-anchored monotonic; merging across processes assumes loosely
synced clocks (the trails are for humans, not for skew-corrected profiling —
that is ``/debug/trace``'s job).

Stdlib-only on purpose (no jax, no repo imports): runnable on a laptop
against bundles scp'd off an incident.

Usage::

    python tools/postmortem.py bundle.json                 # summary
    python tools/postmortem.py bundle.json --list          # requests seen
    python tools/postmortem.py bundle.json --req rtr-3     # one trail
    python tools/postmortem.py router.json replica.json --req rtr-3
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional

__all__ = ["load_bundles", "merged_events", "request_ids", "timeline_for",
           "attribution_for", "render_timeline", "main"]


def load_bundles(paths: List[str]) -> List[Dict]:
    bundles = []
    for path in paths:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        if "events" not in doc or "trigger" not in doc:
            raise ValueError(f"{path}: not a postmortem bundle (no events/trigger)")
        doc["_path"] = path
        bundles.append(doc)
    return bundles


def _tier_of(bundle: Dict, name: str) -> str:
    """Which tier produced one event: router.* events are router-tier even
    inside a replica-tagged in-process bundle (the recorder is shared)."""
    if name.startswith("router."):
        return "router"
    if name.startswith(("sched.", "supervisor.")):
        return "serving"
    if name.startswith(("admit.", "chunk.", "migrate.")) or name == "preempt":
        return "engine"
    return bundle.get("tier", "?")


def merged_events(bundles: List[Dict]) -> List[Dict]:
    """Every bundle's flight events, tier-tagged and sorted by timestamp.
    Duplicate (same-seq, same-pid) events across two dumps of one process
    collapse, so overlapping bundles don't double every line."""
    seen = set()
    out = []
    for b in bundles:
        for ev in b.get("events", ()):
            # the timestamp disambiguates two processes whose pids collide
            # (recycled pid, bundles from different hosts): same-process dumps
            # of one event repeat t exactly, distinct processes never do
            key = (b.get("pid"), ev.get("seq"), ev.get("name"), ev.get("t"))
            if key in seen:
                continue
            seen.add(key)
            ev = dict(ev)
            ev["_tier"] = _tier_of(b, ev.get("name", ""))
            out.append(ev)
    out.sort(key=lambda e: e.get("t", 0.0))
    return out


def _matches(ev: Dict, key: str) -> bool:
    if ev.get("trace") == key:
        return True
    rid = ev.get("req_id")
    # "req_id:N" is the key --list prints for trace-less events — every
    # listed selector must round-trip through --req
    return rid is not None and key in (str(rid), f"req-{rid}", f"req_id:{rid}")


def request_ids(bundles: List[Dict]) -> Dict[str, Dict[str, int]]:
    """{trace-or-req key: {tier: event count}} over every bundle."""
    out: Dict[str, Dict[str, int]] = {}
    for ev in merged_events(bundles):
        key = ev.get("trace")
        if key is None and ev.get("req_id") is not None:
            key = f"req_id:{ev['req_id']}"
        if key is None:
            continue
        per = out.setdefault(key, {})
        per[ev["_tier"]] = per.get(ev["_tier"], 0) + 1
    return out


def timeline_for(bundles: List[Dict], key: str) -> List[Dict]:
    """One request's cross-tier timeline: its flight events (router +
    replica, joined on the trace id) merged with its spans, sorted by
    timestamp. Each entry: {"t", "kind": "event"|"span", "tier", "name",
    ...original fields}."""
    entries: List[Dict] = []
    for ev in merged_events(bundles):
        if _matches(ev, key):
            e = dict(ev)
            e["kind"] = "event"
            e["tier"] = e.pop("_tier")
            entries.append(e)
    seen_spans = set()
    for b in bundles:
        for sp in b.get("spans", ()):
            if sp.get("trace") != key:
                continue
            skey = (sp.get("name"), sp.get("ts"), sp.get("tid"))
            if skey in seen_spans:
                continue
            seen_spans.add(skey)
            entries.append({"kind": "span", "tier": b.get("tier", "?"),
                            "name": sp.get("name"), "t": sp.get("ts", 0.0),
                            "dur": sp.get("dur"), "args": sp.get("args")})
    entries.sort(key=lambda e: e.get("t", 0.0))
    return entries


def attribution_for(bundles: List[Dict], key: str) -> Optional[Dict]:
    """The request's latency-attribution record from any bundle's
    finished-request tail (replica bundles carry it in
    health.recent_finished)."""
    for b in bundles:
        for row in (b.get("health") or {}).get("recent_finished", ()) or ():
            if row.get("trace") == key or str(row.get("req_id")) == key:
                return row
    return None


def render_timeline(entries: List[Dict]) -> List[str]:
    """Human-readable trail lines, one per entry, t-relative to the first."""
    if not entries:
        return ["  (no events or spans for this request)"]
    t0 = entries[0].get("t", 0.0)
    lines = []
    for e in entries:
        dt = (e.get("t", 0.0) - t0) * 1e3
        extra = {k: v for k, v in e.items()
                 if k not in ("t", "kind", "tier", "name", "seq", "trace", "args", "dur")}
        if e.get("dur") is not None:
            extra["dur_ms"] = round(e["dur"] * 1e3, 3)
        if e.get("args"):
            extra.update(e["args"])
        detail = " ".join(f"{k}={v}" for k, v in sorted(extra.items()))
        lines.append(f"  +{dt:10.3f}ms  [{e['tier']:>7}] {e['kind']:<5} "
                     f"{e['name']:<24} {detail}".rstrip())
    return lines


def _top_tenant_line(bundle: Dict) -> Optional[str]:
    """One headline from the bundle's rolling usage aggregate: who was
    burning the most billed tokens when the incident fired. None when the
    bundle predates usage metering (or is router-tier)."""
    usage = (bundle.get("health") or {}).get("usage") or {}
    tenants = usage.get("tenants") or {}
    if not tenants:
        return None
    def billed(b):
        return (b.get("prompt_tokens", 0) - b.get("cached_tokens", 0)
                + b.get("completion_tokens", 0))
    top, bucket = max(tenants.items(), key=lambda kv: billed(kv[1]))
    return (f"usage: {usage.get('records', 0)} records, top tenant "
            f"{top} ({billed(bucket)} billed tokens, "
            f"{bucket.get('records', 0)} requests)")


def _summary(bundles: List[Dict]) -> List[str]:
    lines = []
    for b in bundles:
        health = b.get("health") or {}
        lines.append(f"{b['_path']}:")
        lines.append(f"  tier={b.get('tier')} trigger={b.get('trigger')} "
                     f"wall_time={b.get('wall_time')}")
        if b.get("detail"):
            lines.append(f"  detail: {json.dumps(b['detail'])[:200]}")
        lines.append(f"  events={len(b.get('events', []))} "
                     f"(dropped {b.get('events_dropped', 0)}), "
                     f"spans={len(b.get('spans', []))} "
                     f"(dropped {b.get('spans_dropped', 0)})")
        for k in ("loop_state", "pending", "slot_quarantines", "policy"):
            if k in health:
                lines.append(f"  {k}={health[k]}")
        top = _top_tenant_line(b)
        if top is not None:
            lines.append(f"  {top}")
        tail = health.get("ttft_tail") or {}
        if tail:
            # where the worst tenth's time to first token went (the replica
            # sums it over its finished tail: engine_loop.ttft_tail)
            shares = " ".join(f"{k}={v:.1f}%" for k, v in tail["share"].items())
            lines.append(f"  ttft tail: worst {tail['count']} of {tail['requests']} from "
                         f"{tail['ttft_min_ms']:.1f}ms, {tail['steps_mean']:.1f} steps: {shares}")
    return lines


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    req = None
    if "--req" in argv:
        i = argv.index("--req")
        if i + 1 >= len(argv):
            print(__doc__)
            return 2
        req = argv[i + 1]
        del argv[i:i + 2]
    list_mode = "--list" in argv
    paths = [a for a in argv if not a.startswith("--")]
    if not paths:
        print(__doc__)
        return 2
    bundles = load_bundles(paths)
    if req is not None:
        entries = timeline_for(bundles, req)
        print(f"decision trail for {req} "
              f"({sum(1 for e in entries if e['kind'] == 'event')} events, "
              f"{sum(1 for e in entries if e['kind'] == 'span')} spans):")
        for line in render_timeline(entries):
            print(line)
        row = attribution_for(bundles, req)
        if row is not None and row.get("attribution"):
            e2e = (row.get("finish_t") or 0) - (row.get("arrival_t") or 0)
            print(f"latency attribution (e2e {e2e * 1e3:.1f}ms, "
                  f"finish_reason={row.get('finish_reason')}):")
            for phase, v in row["attribution"].items():
                print(f"  {phase:<16} {v * 1e3:10.3f}ms")
        else:
            print("latency attribution: not in these bundles "
                  "(request unfinished at dump time, or router-only bundle)")
        return 0
    if list_mode:
        for b in bundles:
            top = _top_tenant_line(b)
            if top is not None:
                print(f"{b['_path']}: {top}")
        for key, per in sorted(request_ids(bundles).items()):
            counts = " ".join(f"{t}={n}" for t, n in sorted(per.items()))
            print(f"{key:<16} {counts}")
        return 0
    for line in _summary(bundles):
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
