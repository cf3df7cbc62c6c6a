"""Device time of the serving step programs by the ``jax.named_scope`` of their
operations, for programs whose layers carry the windowed grouped-query kinds'
scopes (``experimental/window_model.py``, ``transformers/window_layers.py``):
``qk_norm``, ``paged_attn_window`` and ``kv_write/window_plane`` beside ``qkv``,
``rope``, ``kv_write``, ``paged_attn``, ``router``, ``experts``, ``shared_expert``
and the dense step's. The scope of a device operation is taken as
``program_spans.py`` takes it: from the ``tf_op`` stat of its metadata, the
innermost known scope on the path; an enclosing ``while`` keeps only what its
body does not cover.

For the roofline share of the paged kernel the decode launches inside the traced
span are found as ``program_spans.reduce`` finds them: the program's own launch
spans (``TRACER``) laid on the trace's clock by the spans both records hold,
their ``attn_kv_full`` and ``attn_kv_window`` args summed, and the device time
of ``ragged_paged_attention`` in decode-program runs that start inside them.

Read once a run and kept in ``run``. A program without these scopes (any other
configuration's, or a parent commit's) gives None."""

from __future__ import annotations

import statistics

from .common import log
from .latent_scopes import config_of, counter_delta  # noqa: F401  (the same two reads of a run, for this kind's metric files)
from .program_spans import DECODE_MODULE, MODULE_ID, PAGED_KERNEL, _Cover, _self_times, clock_offsets, read_xplane
from .trace_reduce import _union

PROGRAMS = (DECODE_MODULE, "jit__mixed_flat_impl")
WINDOW_SCOPES = ("qk_norm", "paged_attn_window")  # what only these kinds' programs carry
FULL_ATTN = ("paged_attn",)
WINDOW_ATTN = ("paged_attn_window", "window_plane")  # the window's kernel call and the write into its plane
SCOPES = WINDOW_SCOPES + ("window_plane", "embed", "attn_norm", "qkv", "rope", "kv_write", "paged_attn", "attn_gather",
                          "o_proj", "mlp_norm", "mlp", "router", "experts", "shared_expert", "final_norm", "lm_head",
                          "sample", "bookkeeping")
COUNTS = ("attn_kv_full", "attn_kv_window")


def scope_of(op_name):
    if not op_name:
        return None
    parts = op_name.rstrip(":").split("/")[:-1]
    return next((p for p in reversed(parts) if p in SCOPES), None)


def reduce(doc, spans=()):
    """{"ns_by_scope", "ns", "decode"} of the step programs' operations in
    ``doc`` (``program_spans.read_xplane``), or None where none carries a
    windowed kind's scope. ``decode`` is {"launches", "attn_kv_full",
    "attn_kv_window", "kernel_ns"} over the decode launch spans of ``spans``
    (TRACER spans as dicts) that lie inside the traced span and carry the two
    counts, or None where there are none."""
    names = {m.group(2): m.group(1) for m in (MODULE_ID.match(n) for n, _, _ in doc["modules"])
             if m and m.group(1) in PROGRAMS}
    launches, cover = [], None
    offsets = clock_offsets(doc, spans) if spans else []
    if offsets:
        offset = statistics.median(offsets)
        lo, hi = doc["extent_ns"]
        at = lambda t_s: t_s * 1e9 + offset
        launches = [s for s in spans if s.get("cat") == "engine" and s["name"] == "decode"
                    and all(c in (s.get("args") or {}) for c in COUNTS)
                    and at(s["ts"]) >= lo and at(s["ts"] + s["dur"]) <= hi]
        cover = _Cover(_union([(at(s["ts"]), at(s["ts"] + s["dur"])) for s in launches]))
    by_scope, kernel = {}, 0.0
    for i, own in _self_times(doc["ops"]):
        name, start, _, op_name, program = doc["ops"][i]
        if program not in names:
            continue
        scope = scope_of(op_name) or "unscoped"
        by_scope[scope] = by_scope.get(scope, 0.0) + own
        if (launches and names[program] == DECODE_MODULE and name.startswith(PAGED_KERNEL)
                and cover.of(start, start + 1) > 0):
            kernel += own
    if not any(s in by_scope for s in WINDOW_SCOPES):
        return None
    decode = dict({"launches": len(launches), "kernel_ns": kernel},
                  **{c: sum(s["args"][c] for s in launches) for c in COUNTS}) if launches else None
    return {"ns_by_scope": by_scope, "ns": sum(by_scope.values()), "decode": decode}


def table(run):
    """``reduce`` over this run's trace and the program's own spans, once; kept in ``run`` and logged."""
    if "window_scopes" not in run:
        run["window_scopes"] = None
        try:
            if run.get("kind") == "serve" and run.get("tracer") is not None:
                from paddlenlp_tpu.observability.tracer import TRACER

                spans = [s.to_dict() for s in TRACER.snapshot()]
                run["window_scopes"] = reduce(read_xplane(run["tracer"].xplane_path()), spans)
        except Exception as e:  # a reader that finds nothing returns nothing
            log(phase="window_scopes", error=repr(e)[:300])
        t = run["window_scopes"]
        if t:
            log(phase="window_scopes", device_ms=round(t["ns"] / 1e6, 3), decode=t["decode"],
                ms_by_scope={k: round(v / 1e6, 3) for k, v in sorted(t["ns_by_scope"].items(), key=lambda kv: -kv[1])})
    return run["window_scopes"]


def share(run, scopes):
    """Percent of the step programs' device time under ``scopes`` in the traced span, or None."""
    t = table(run)
    if not t or not t["ns"]:
        return None
    return sum(t["ns_by_scope"].get(s, 0.0) for s in scopes) / t["ns"] * 100.0
