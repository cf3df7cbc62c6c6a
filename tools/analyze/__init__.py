"""Static-analysis suite for the repo's hand-rolled correctness contracts.

Three contracts in this codebase historically held only by reviewer
vigilance, and each has been broken (and caught by hand) at least once:

- **jit purity** — every function reachable from a ``jax.jit``/``pjit`` call
  or a Pallas kernel must stay side-effect free (no ``print``/``time.*``/
  ``np.random``/logging, no instance-state mutation): impurity silently runs
  at trace time only, so "it worked once" is exactly the failure mode;
- **host-sync discipline** — the engine step path must not grow silent
  device→host syncs (``.item()``, ``np.asarray``, ``block_until_ready``,
  host bincounts): the PR 5/7 perf work caught ``one_hot``/host-bincount
  regressions by hand, twice;
- **sharding contract** — every jitted step program of the sharded backend
  carries explicit ``in_shardings``/``out_shardings``/``donate_argnums``
  (the PR 8 contract), and the sharded jit set never drifts from the base;
- plus **lock discipline** over the serving stack's shared state and the
  **catalog consistency** lints (faults / trace spans / metric names).

This package turns those contracts into machines: an AST-based (stdlib
``ast``, **no jax import**, no repo imports at package scope) checker
framework with a pluggable registry, per-checker findings carrying
``file:line`` + a rule id, and a committed baseline file implementing a
**ratchet** — existing violations are frozen in ``BASELINE.json`` with a
justification; any NEW violation fails tier-1
(``tests/tools/test_analyze.py`` runs the suite).

Run it::

    python -m tools.analyze                 # one JSON summary line, rc=1 on new findings
    python -m tools.analyze --format text   # human-readable findings
    python -m tools.analyze --checker jit-purity
    python -m tools.analyze --write-baseline  # freeze current findings (justify by hand!)

Inline allowlists (each requires a reason, read by humans in review):

- ``# sync-ok: <reason>`` — a documented host-sync point (host_sync checker);
- ``# lock-ok: <reason>`` — a deliberate unguarded access (lock_discipline);
- ``# jit-ok: <reason>``  — a deliberate trace-time side effect (jit_purity);
- ``# span-names: a b c`` — literal names behind a dynamic span call site.
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import os
from typing import Callable, Dict, List, Optional

__all__ = ["Finding", "Checker", "AnalysisContext", "CHECKERS", "register",
           "run_checkers", "DEFAULT_CONFIG"]


# --------------------------------------------------------------------- config
#: Per-checker knobs, overridable via AnalysisContext(config=...). Paths are
#: repo-root-relative with "/" separators (normalized at use).
DEFAULT_CONFIG: Dict = {
    # directories the generic scanners walk
    "scan_dirs": ["paddlenlp_tpu", "tools"],
    # jit_purity: where the call graph is built (keep this bounded — a
    # name-based graph over the whole package would alias unrelated helpers)
    "jit_graph_dirs": [
        "paddlenlp_tpu/experimental",
        "paddlenlp_tpu/ops",
        "paddlenlp_tpu/quantization",
        "paddlenlp_tpu/parallel",
    ],
    # host_sync: file -> hot-path function qualnames ("Class.method" / "func").
    # These are the engine step path: everything that runs once per engine
    # step under serving traffic. Host-side-by-design code (the speculative
    # proposers / rejection sampler, admission bookkeeping off the step loop)
    # is deliberately NOT listed — its host math is the documented algorithm.
    "host_sync_paths": {
        "paddlenlp_tpu/experimental/engine.py": [
            "InferenceEngine.step", "InferenceEngine._admit",
            "InferenceEngine._admit_slots", "InferenceEngine._bind_waiting",
            "InferenceEngine._admit_chunked", "InferenceEngine._prefill_batch",
            "InferenceEngine._mixed_step", "InferenceEngine._mixed_rows",
            "InferenceEngine._mixed_settle", "InferenceEngine._decode_running",
            "InferenceEngine._decode_settle", "InferenceEngine._decode_spec",
            "InferenceEngine._spec_accept", "InferenceEngine._settle_sampled",
            "InferenceEngine._advance_migrations",
            "InferenceEngine._advance_promotions",
            "InferenceEngine._drain_spills",
            "InferenceEngine._emit", "InferenceEngine._free_kv",
            "InferenceEngine._preempt",
        ],
        "paddlenlp_tpu/experimental/kv_host_tier.py": [
            "HostKVTier.put", "HostKVTier.take", "_SpillBatch.settle",
        ],
        "paddlenlp_tpu/experimental/backend.py": [
            "ModelBackend.migration_ready", "ModelBackend.kv_writeback",
            "launch_geometry", "samp_arrays", "SingleDeviceBackend._send", "SingleDeviceBackend._to_device",
            "SingleDeviceBackend.prefill", "SingleDeviceBackend.decode",
            "SingleDeviceBackend.verify", "SingleDeviceBackend.mixed_step",
            "SingleDeviceBackend.mixed_step_begin",
            "SingleDeviceBackend._mixed_flat_launch",
            "SingleDeviceBackend._cached_counts", "SingleDeviceBackend.seed_counts",
            "SingleDeviceBackend.reset_counts", "SingleDeviceBackend.apply_cow",
            "SingleDeviceBackend.kv_spill", "SingleDeviceBackend.kv_promote",
        ],
        "paddlenlp_tpu/experimental/launch_pack.py": ["pack", "layout_of"],
        "paddlenlp_tpu/experimental/sharded_backend.py": [
            "ShardedBackend.params", "ShardedBackend._place_launch",
        ],
        "paddlenlp_tpu/experimental/disagg_backend.py": [
            "DisaggBackend.prefill", "DisaggBackend.decode",
            "DisaggBackend.verify", "DisaggBackend.mixed_step",
            "DisaggBackend.seed_counts", "DisaggBackend.reset_counts",
            "DisaggBackend.apply_cow", "DisaggBackend.kv_migrate",
            "DisaggBackend.kv_spill", "DisaggBackend.kv_promote",
            "DisaggBackend.kv_writeback",
        ],
        "paddlenlp_tpu/serving/engine_loop.py": [
            "EngineLoop._run_iteration", "EngineLoop._drain_cmds",
            "EngineLoop._finish", "EngineLoop._make_stream_cb",
        ],
    },
    # sharding_contract: the base jit builder and the sharded overrides
    "sharding_base_file": "paddlenlp_tpu/experimental/inference_model.py",
    "sharding_sharded_file": "paddlenlp_tpu/experimental/sharded_backend.py",
    # files held to the FULL contract (in/out shardings + donation on every
    # jit): the sharded backend's step programs and the disagg backend's
    # migration gather/scatter programs (both stages' step programs are the
    # sharded file's — each stage IS a ShardedBackend)
    "sharding_strict_files": [
        "paddlenlp_tpu/experimental/sharded_backend.py",
        "paddlenlp_tpu/experimental/disagg_backend.py",
    ],
    "sharding_extra_dirs": ["paddlenlp_tpu/experimental"],
    # lock_discipline scans every file in scan_dirs for "# guarded-by:" lines
    # catalogs
    "faults_module": "paddlenlp_tpu/utils/faults.py",
    "span_catalog_module": "paddlenlp_tpu/observability/span_catalog.py",
    "event_catalog_module": "paddlenlp_tpu/observability/event_catalog.py",
    "catalog_src_dir": "paddlenlp_tpu",
    "readme_paths": ["README.md", "paddlenlp_tpu/serving/README.md"],
}


# -------------------------------------------------------------------- findings
@dataclasses.dataclass
class Finding:
    """One rule violation. ``fingerprint`` deliberately excludes the line
    number so baselined findings survive unrelated edits above them; the
    ``message`` should therefore carry a stable snippet of the offending
    construct, not positional info."""

    rule: str
    file: str  # repo-root-relative, "/" separators
    line: int
    scope: str  # enclosing qualname ("Class.method", "func", or "<module>")
    message: str

    @property
    def fingerprint(self) -> str:
        key = f"{self.rule}|{self.file}|{self.scope}|{self.message}"
        return hashlib.sha256(key.encode()).hexdigest()[:16]

    def to_dict(self) -> Dict:
        return {"rule": self.rule, "file": self.file, "line": self.line,
                "scope": self.scope, "message": self.message,
                "fingerprint": self.fingerprint}

    def render(self) -> str:
        return f"{self.file}:{self.line}: [{self.rule}] {self.scope}: {self.message}"


@dataclasses.dataclass
class Checker:
    name: str
    description: str
    run: Callable[["AnalysisContext"], List[Finding]]


#: name -> Checker; populated by importing tools.analyze.checkers
CHECKERS: Dict[str, Checker] = {}


def register(name: str, description: str):
    """Decorator: register ``fn(ctx) -> [Finding]`` as a named checker."""

    def deco(fn):
        CHECKERS[name] = Checker(name, description, fn)
        return fn

    return deco


# --------------------------------------------------------------------- context
class AnalysisContext:
    """Shared parse cache + config for one analysis run.

    Checkers see one immutable-ish facade: ``iter_py`` to enumerate sources,
    ``tree``/``lines`` cached per file (every checker walking the same file
    parses it once), ``allowed(relpath, line, marker)`` for the inline
    allowlist convention (marker comment on the flagged line or the line
    directly above it, reason required).
    """

    def __init__(self, root: str, config: Optional[Dict] = None):
        self.root = os.path.abspath(root)
        self.config: Dict = dict(DEFAULT_CONFIG)
        if config:
            self.config.update(config)
        self._sources: Dict[str, str] = {}
        self._lines: Dict[str, List[str]] = {}
        self._trees: Dict[str, Optional[ast.Module]] = {}
        self.parse_errors: List[Finding] = []

    # ------------------------------------------------------------- file access
    def abspath(self, rel: str) -> str:
        return os.path.join(self.root, rel.replace("/", os.sep))

    def exists(self, rel: str) -> bool:
        return os.path.isfile(self.abspath(rel))

    def iter_py(self, subdirs: Optional[List[str]] = None) -> List[str]:
        """Repo-relative paths of every .py under ``subdirs`` (default: the
        configured scan_dirs), sorted for deterministic output."""
        out = []
        for sub in subdirs if subdirs is not None else self.config["scan_dirs"]:
            base = self.abspath(sub)
            if os.path.isfile(base) and base.endswith(".py"):
                out.append(sub)
                continue
            for dirpath, dirnames, filenames in os.walk(base):
                dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                for name in filenames:
                    if name.endswith(".py"):
                        full = os.path.join(dirpath, name)
                        out.append(os.path.relpath(full, self.root).replace(os.sep, "/"))
        return sorted(set(out))

    def source(self, rel: str) -> str:
        if rel not in self._sources:
            with open(self.abspath(rel), encoding="utf-8") as f:
                self._sources[rel] = f.read()
        return self._sources[rel]

    def lines(self, rel: str) -> List[str]:
        if rel not in self._lines:
            self._lines[rel] = self.source(rel).splitlines()
        return self._lines[rel]

    def tree(self, rel: str) -> Optional[ast.Module]:
        """Parsed AST (cached); None (plus a parse-error finding) on a file
        that does not parse — a syntax error must fail the suite loudly, not
        silently skip every checker."""
        if rel not in self._trees:
            try:
                self._trees[rel] = ast.parse(self.source(rel), filename=rel)
            except SyntaxError as e:
                self._trees[rel] = None
                self.parse_errors.append(Finding(
                    rule="parse-error", file=rel, line=e.lineno or 0,
                    scope="<module>", message=f"file does not parse: {e.msg}"))
        return self._trees[rel]

    # ------------------------------------------------------------- allowlists
    def allowed(self, rel: str, line: int, marker: str) -> bool:
        """True if the 1-indexed ``line`` carries the inline allowlist
        ``marker`` ("sync-ok" / "lock-ok" / "jit-ok") with a non-empty
        reason, or the line above is a comment-only line carrying it. The
        comment-only requirement stops a trailing annotation on one construct
        from silently allowlisting whatever lands on the next line."""
        lines = self.lines(rel)
        for ln, standalone in ((line, False), (line - 1, True)):
            if not 1 <= ln <= len(lines):
                continue
            text = lines[ln - 1]
            if standalone and not text.strip().startswith("#"):
                continue
            idx = text.find(f"# {marker}:")
            if idx >= 0 and text[idx + len(marker) + 3:].strip():
                return True
        return False


# --------------------------------------------------------------------- helpers
def qualname_index(tree: ast.Module) -> Dict[ast.AST, str]:
    """Map every function/class def node to its dotted qualname."""
    out: Dict[ast.AST, str] = {}

    def walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                q = f"{prefix}.{child.name}" if prefix else child.name
                out[child] = q
                walk(child, q)
            else:
                walk(child, prefix)

    walk(tree, "")
    return out


def enclosing_scope(tree: ast.Module, lineno: int) -> str:
    """Qualname of the innermost def/class containing ``lineno``."""
    best, best_span = "<module>", None
    for node, q in qualname_index(tree).items():
        end = getattr(node, "end_lineno", node.lineno)
        if node.lineno <= lineno <= end:
            span = end - node.lineno
            if best_span is None or span <= best_span:
                best, best_span = q, span
    return best


def dotted_name(node: ast.AST) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain, None for anything else."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def str_arg(call: ast.Call, index: int = 0) -> Optional[str]:
    """The ``index``-th positional arg if it is a string literal."""
    if len(call.args) > index and isinstance(call.args[index], ast.Constant) \
            and isinstance(call.args[index].value, str):
        return call.args[index].value
    return None


# ------------------------------------------------------------------ orchestration
def run_checkers(ctx: AnalysisContext, names: Optional[List[str]] = None):
    """Run the selected (default: all) checkers. Returns
    ``(findings, per_checker_counts)`` with parse errors folded in."""
    # checkers self-register on import; do it lazily so the framework module
    # stays importable without the checker set (unit tests stub their own)
    from . import checkers  # noqa: F401

    selected = names or sorted(CHECKERS)
    unknown = [n for n in selected if n not in CHECKERS]
    if unknown:
        raise KeyError(f"unknown checker(s) {unknown}; have {sorted(CHECKERS)}")
    findings: List[Finding] = []
    per: Dict[str, int] = {}
    for name in selected:
        got = list(CHECKERS[name].run(ctx))
        per[name] = len(got)
        findings.extend(got)
    if ctx.parse_errors:
        findings.extend(ctx.parse_errors)
        per["parse-error"] = len(ctx.parse_errors)
    return findings, per
