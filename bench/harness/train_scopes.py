"""Device time of a training step program by the ``jax.named_scope`` of its
operations, and what the step's layers counted, for a Trainer whose model carries
the latent-attention and held-expert scopes (``transformers/deepseek_v3``,
``deepseek_v2/modeling.py:DeepseekV2Attention``, ``latent_layers.experts_grouped``):
``mla_proj``, ``rope``, ``mla_attn``, ``o_proj``, ``router``, ``expert_dispatch``,
``expert_mm``, ``expert_combine``, ``shared_expert``, and flax's module scopes
``mlp`` and ``lm_head``. Forward, recomputed and backward operations carry the same
scope. The scope of a device operation is taken as ``program_spans.py`` takes it:
from the ``tf_op`` stat of its metadata, the innermost known scope on the path; an
enclosing ``while`` or ``conditional`` keeps only what its body does not cover.

The counters are the ``train_step`` spans' args in the program's own ``TRACER`` ring
(the harness runs the Trainer in its own process; the Trainer puts a step's
``expert_assignments``, ``expert_assignments_local``, ``expert_tokens_max`` there
once the step has finished): of the steps that lie inside the profiler's span for
``expert_mm_roofline``, of the steps that ended inside the window for the two shares.

Read once a run and kept in ``run``. A program without these scopes or counters (any
other configuration's, or a parent commit's) gives None."""

from __future__ import annotations

import time

from .common import log
from .program_spans import MODULE_ID, _self_times, read_xplane

PROGRAM = "jit_train_step"
MLA_SCOPES = ("mla_proj", "rope", "mla_attn")
EXPERT_SCOPES = ("router", "expert_dispatch", "expert_mm", "expert_combine", "shared_expert")
SCOPES = MLA_SCOPES + EXPERT_SCOPES + ("o_proj", "mlp", "lm_head", "embed_tokens", "input_layernorm",
                                       "post_attention_layernorm", "norm")
COUNTERS = ("expert_assignments", "expert_assignments_local", "expert_tokens_max")


def scope_of(op_name):
    if not op_name:
        return None
    parts = op_name.rstrip(":").split("/")[:-1]
    return next((p for p in reversed(parts) if p in SCOPES), None)


def reduce(doc):
    """{"ns_by_scope", "ns", "runs"} of the training step program's operations in ``doc``
    (``program_spans.read_xplane``), or None where none carries an expert or latent-attention scope."""
    ids = {m.group(2) for m in (MODULE_ID.match(n) for n, _, _ in doc["modules"]) if m and m.group(1) == PROGRAM}
    runs = sum(1 for n, _, _ in doc["modules"] if (m := MODULE_ID.match(n)) and m.group(1) == PROGRAM)
    by_scope = {}
    for i, own in _self_times(doc["ops"]):
        _, _, _, op_name, program = doc["ops"][i]
        if program in ids:
            scope = scope_of(op_name) or "unscoped"
            by_scope[scope] = by_scope.get(scope, 0.0) + own
    if not any(s in by_scope for s in MLA_SCOPES + EXPERT_SCOPES):
        return None
    return {"ns_by_scope": by_scope, "ns": sum(by_scope.values()), "runs": runs}


def counted(spans, since, until):
    """Sums of the counters over the ``train_step`` spans (dicts, tracer clock) that lie inside [since, until],
    with their number under "steps"; None where no such span carries them."""
    inside = [s["args"] for s in spans if s["name"] == "train_step" and s["ts"] >= since
              and s["ts"] + s["dur"] <= until and all(c in (s.get("args") or {}) for c in COUNTERS)]
    if not inside:
        return None
    return dict({c: sum(a[c] for a in inside) for c in COUNTERS}, steps=len(inside))


def table(run):
    """{"scopes": ``reduce`` of this run's trace, "traced": ``counted`` over the profiler's span,
    "window": ``counted`` over the measurement window}, once; kept in ``run`` and logged."""
    if "train_scopes" not in run:
        run["train_scopes"] = out = {"scopes": None, "traced": None, "window": None}
        try:
            if run.get("kind") == "train" and run.get("tracer") is not None:
                from paddlenlp_tpu.observability.tracer import TRACER

                spans = [s.to_dict() for s in TRACER.snapshot()]
                shift = TRACER.now() - time.monotonic()  # the harness stamps its clock on time.monotonic()
                tracer, ends = run["tracer"], run["step_ends"]
                out["traced"] = counted(spans, tracer.t_start + shift, tracer.t_stop + shift)
                out["window"] = counted(spans, ends[0] + shift, ends[-1] + shift)
                out["scopes"] = reduce(read_xplane(tracer.xplane_path()))
        except Exception as e:  # a reader that finds nothing returns nothing
            log(phase="train_scopes", error=repr(e)[:300])
        if out["scopes"]:
            t = out["scopes"]
            log(phase="train_scopes", device_ms=round(t["ns"] / 1e6, 3), step_runs=t["runs"], traced=out["traced"],
                window=out["window"],
                ms_by_scope={k: round(v / 1e6, 3) for k, v in sorted(t["ns_by_scope"].items(), key=lambda kv: -kv[1])})
    return run["train_scopes"]


def share(run, scopes):
    """Percent of the step program's device time under ``scopes`` in the traced span, or None."""
    t = table(run)["scopes"]
    if not t or not t["ns"]:
        return None
    return sum(t["ns_by_scope"].get(s, 0.0) for s in scopes) / t["ns"] * 100.0
