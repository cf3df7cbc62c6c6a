"""Device time of the serving step programs by the ``jax.named_scope`` of their
operations, for programs that generate by diffusion over blocks
(``experimental/block_model.py``): ``denoise``, ``commit``, ``confidence`` and
``unmask`` beside ``qkv``, ``qk_norm``, ``rope``, ``kv_write``, ``paged_attn``,
``router``, ``experts``, ``lm_head`` and the dense step's. The scope of a device
operation is taken as ``program_spans.py`` takes it: from the ``tf_op`` stat of
its metadata, the innermost known scope on the path; an enclosing ``while``
keeps only what its body does not cover.

For the time of a pass and the roofline share of the paged kernel the decode
launches inside the traced span are found as ``program_spans.reduce`` finds
them: the program's own launch spans (``TRACER``) laid on the trace's clock by
the spans both records hold, their ``steps`` (passes a launch) and
``attn_kv_visible`` args summed, and the device time of the decode program's
operations, and of ``ragged_paged_attention`` among them, in runs that start
inside those launches.

Read once a run and kept in ``run``. A program without these scopes (any other
configuration's, or a parent commit's) gives None."""

from __future__ import annotations

import statistics

from .common import log
from .latent_scopes import config_of, counter_delta  # noqa: F401  (the same two reads of a run, for this kind's metric files)
from .program_spans import DECODE_MODULE, MODULE_ID, PAGED_KERNEL, _Cover, _self_times, clock_offsets, read_xplane
from .trace_reduce import _union

PROGRAMS = (DECODE_MODULE, "jit__mixed_flat_impl")
DIFFUSION_SCOPES = ("confidence", "unmask", "commit", "denoise")  # what only this kind's programs carry
BLOCK_ATTN = ("paged_attn",)
CONFIDENCE = ("confidence", "unmask")
SCOPES = DIFFUSION_SCOPES + ("embed", "attn_norm", "qkv", "qk_norm", "rope", "kv_write", "paged_attn", "attn_gather",
                             "o_proj", "mlp_norm", "router", "experts", "final_norm", "lm_head", "bookkeeping")
COUNTS = ("steps", "attn_kv_visible")


def scope_of(op_name):
    if not op_name:
        return None
    parts = op_name.rstrip(":").split("/")[:-1]
    return next((p for p in reversed(parts) if p in SCOPES), None)


def reduce(doc, spans=()):
    """{"ns_by_scope", "ns", "decode"} of the step programs' operations in
    ``doc`` (``program_spans.read_xplane``), or None where none carries a scope
    of this kind's own. ``decode`` is {"launches", "steps", "attn_kv_visible",
    "program_ns", "kernel_ns"} over the decode launch spans of ``spans`` (TRACER
    spans as dicts) that lie inside the traced span and carry the two counts, or
    None where there are none."""
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
    by_scope, program, kernel = {}, 0.0, 0.0
    for i, own in _self_times(doc["ops"]):
        name, start, _, op_name, module = doc["ops"][i]
        if module not in names:
            continue
        scope = scope_of(op_name) or "unscoped"
        by_scope[scope] = by_scope.get(scope, 0.0) + own
        if launches and names[module] == DECODE_MODULE and cover.of(start, start + 1) > 0:
            program += own
            if name.startswith(PAGED_KERNEL):
                kernel += own
    if not any(s in by_scope for s in DIFFUSION_SCOPES):
        return None
    decode = dict({"launches": len(launches), "program_ns": program, "kernel_ns": kernel},
                  **{c: sum(s["args"][c] for s in launches) for c in COUNTS}) if launches else None
    return {"ns_by_scope": by_scope, "ns": sum(by_scope.values()), "decode": decode}


def table(run):
    """``reduce`` over this run's trace and the program's own spans, once; kept in ``run`` and logged."""
    if "diffusion_scopes" not in run:
        run["diffusion_scopes"] = None
        try:
            if run.get("kind") == "serve" and run.get("tracer") is not None:
                from paddlenlp_tpu.observability.tracer import TRACER

                spans = [s.to_dict() for s in TRACER.snapshot()]
                run["diffusion_scopes"] = reduce(read_xplane(run["tracer"].xplane_path()), spans)
        except Exception as e:  # a reader that finds nothing returns nothing
            log(phase="diffusion_scopes", error=repr(e)[:300])
        t = run["diffusion_scopes"]
        if t:
            log(phase="diffusion_scopes", device_ms=round(t["ns"] / 1e6, 3), decode=t["decode"],
                ms_by_scope={k: round(v / 1e6, 3) for k, v in sorted(t["ns_by_scope"].items(), key=lambda kv: -kv[1])})
    return run["diffusion_scopes"]


def share(run, scopes):
    """Percent of the step programs' device time under ``scopes`` in the traced span, or None."""
    t = table(run)
    if not t or not t["ns"]:
        return None
    return sum(t["ns_by_scope"].get(s, 0.0) for s in scopes) / t["ns"] * 100.0
