"""Device time of the serving step programs by the ``jax.named_scope`` of their
operations, for programs whose layers carry the latent kinds' scopes
(``experimental/latent_model.py``, ``transformers/latent_layers.py``):
``mla_proj``, ``indexer``, ``index_topk``, ``latent_gather``, ``mla_attn``,
``window_attn``, ``attn_gate``, ``router``, ``experts``, ``shared_expert`` and
``kv_write`` beside the dense step's. The scope of a device operation is taken
as ``program_spans.py`` takes it: from the ``tf_op`` stat of its metadata, the
innermost known scope on the path; an enclosing ``while`` keeps only what its
body does not cover. Read once a run and kept in ``run``. A program without
these scopes (any other configuration's, or a parent commit's) gives None."""

from __future__ import annotations

import os

from . import loader
from .common import log
from .program_spans import MODULE_ID, _self_times, read_xplane

PROGRAMS = ("jit__decode_impl", "jit__mixed_flat_impl")
LATENT_SCOPES = ("mla_proj", "indexer", "index_topk", "latent_gather", "mla_attn", "window_attn", "attn_gate",
                 "router", "experts", "shared_expert")
SCOPES = LATENT_SCOPES + ("embed", "attn_norm", "kv_write", "o_proj", "mlp_norm", "mlp", "final_norm", "lm_head",
                          "sample", "bookkeeping")


def scope_of(op_name):
    if not op_name:
        return None
    parts = op_name.rstrip(":").split("/")[:-1]
    return next((p for p in reversed(parts) if p in SCOPES), None)


def reduce(doc):
    """{"ns_by_scope", "ns"} of the step programs' operations in ``doc``
    (``program_spans.read_xplane``), or None where none carries a latent scope."""
    ids = {m.group(2) for m in (MODULE_ID.match(n) for n, _, _ in doc["modules"]) if m and m.group(1) in PROGRAMS}
    by_scope = {}
    for i, own in _self_times(doc["ops"]):
        _, _, _, op_name, program = doc["ops"][i]
        if program in ids:
            scope = scope_of(op_name) or "unscoped"
            by_scope[scope] = by_scope.get(scope, 0.0) + own
    if not any(s in by_scope for s in LATENT_SCOPES):
        return None
    return {"ns_by_scope": by_scope, "ns": sum(by_scope.values())}


def share(run, scopes):
    """Percent of the step programs' device time under ``scopes`` in the traced span, or None."""
    if "latent_scopes" not in run:
        run["latent_scopes"] = None
        try:
            if run.get("kind") == "serve" and run.get("tracer") is not None:
                run["latent_scopes"] = reduce(read_xplane(run["tracer"].xplane_path()))
        except Exception as e:  # a reader that finds nothing returns nothing
            log(phase="latent_scopes", error=repr(e)[:300])
        if run["latent_scopes"]:
            t = run["latent_scopes"]
            log(phase="latent_scopes", device_ms=round(t["ns"] / 1e6, 3),
                ms_by_scope={k: round(v / 1e6, 3) for k, v in sorted(t["ns_by_scope"].items(), key=lambda kv: -kv[1])})
    t = run["latent_scopes"]
    if not t or not t["ns"]:
        return None
    return sum(t["ns_by_scope"].get(s, 0.0) for s in scopes) / t["ns"] * 100.0


def counter_delta(run, key):
    """A ledger total's growth over the window (``/debug/efficiency``, two scrapes), or None where the program has no such total."""
    if run.get("kind") != "serve" or key not in run["after"]["ledger"]:
        return None
    return run["after"]["ledger"][key] - run["before"]["ledger"].get(key, 0)


def config_of(run):
    """The configuration of the cell a traced serving run was made for, or None. The run carries neither
    its cell nor its configuration: the cell is the one its trace was written for (``bench_trace/<workload>``),
    as ``bench/metrics/paged_attn_roofline.py`` recovers it."""
    if run.get("kind") != "serve" or run.get("tracer") is None:
        return None
    return loader.cell(os.path.basename(run["tracer"].dir))["config"]
