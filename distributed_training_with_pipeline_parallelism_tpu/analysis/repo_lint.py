"""AST-based repo lint: project rules the test suite cannot see.

Each rule encodes a contract documented elsewhere in the repo
(docs/static_analysis.md explains how to add more):

``scan-body-host-call``
    No ``time.time()`` / ``time.perf_counter()``, ``.item()``, or
    ``np.asarray`` / ``numpy.asarray`` inside tick/scan bodies — a host
    sync or host-side constant inside a traced loop body either fails
    under jit or silently re-traces. A "tick/scan body" is any function
    passed to ``lax.scan`` / ``lax.fori_loop`` / ``lax.while_loop``
    (positionally or by name), any function named ``tick``, and every
    function nested inside one. ``jnp.asarray`` is fine (traced).

``init-lazy-exports``
    Package ``__init__.py`` files must not eagerly import submodules:
    re-exports go through the ``_LAZY`` + ``__getattr__`` pattern of the
    top-level ``__init__`` so ``import dtpp`` pulls no subsystem. The
    allowlisted eager imports are ``utils.config`` (pure-python dataclasses
    the one-import surface needs at definition time) and
    ``utils.profiling`` (the host recorder: it stamps the clock at its own
    top, before its ``import jax``, so the ``setup/import`` span covers the
    package's import whole — the package's ``__init__`` reads no clock
    itself, see ``raw-step-timing``).

``jit-named-scope``
    No bare ``jax.jit`` in ``parallel/`` modules without a
    ``jax.named_scope`` somewhere in the same file: profile legibility
    (docs/observability.md) requires every jitted entry point to carry
    named scopes so XProf timelines attribute time to pipeline phases.

``raw-tick-table``
    No constructing or mutating raw ``[T, D, 17]`` tick tables outside
    ``analysis/`` and the schedule compilers (``parallel/schedules.py``,
    ``parallel/native.py``): flagged are ``np``/``numpy``/``jnp``
    ``full``/``zeros``/``ones``/``empty`` calls whose shape mentions
    ``N_COLS``, subscript *stores* indexed by a ``COL_*`` column
    constant, and ``.at[...COL_*...].set/add`` updates. Reading table
    cells (``row[COL_FWD_V]``) stays legal everywhere — the executor
    does exactly that. Everything else must go through
    ``compile_schedule``/``compile_order`` or a certified schedule
    artifact, which is what makes the static certification meaningful
    (docs/static_analysis.md "Schedule compiler").

``tp-bare-collective``
    No bare ``jax.lax.all_gather`` / ``jax.lax.psum_scatter`` *calls* in
    ``parallel/tensor_parallel.py`` outside the collective-matmul
    wrappers (``tp_all_gather_matmul`` / ``tp_matmul_reduce_scatter``).
    The wrappers are the single dispatch point for the ``tp_overlap``
    knob (docs/performance.md "Comm/compute overlap") — a bare call
    elsewhere silently bypasses the ring overlap path. Reads/mentions
    of the names stay legal; only call sites are flagged.

``dynamics-sync-read``
    No host fetch (``jax.device_get``, ``jax.block_until_ready``, or a
    ``float(...)`` coercion) of a training-dynamics statistic —
    identifiers or dict keys like ``sq_mb``, ``grad_norm_per_stage``,
    ``nonfinite_per_stage``, ``last_bad_stage``, ``dyn_latest`` —
    outside the modules that own the log-sync boundary
    (``utils/train.py``, ``utils/dynamics.py``) and the off-the-clock
    sweep probe (``utils/sweep.py``). The dynamics contract
    (docs/observability.md §7) is that per-stage stats live in
    device-resident buffers and are read **only** when the loss is
    synced anyway; a fetch anywhere else adds a device round-trip per
    step and silently serializes the pipeline.

``raw-step-timing``
    No direct host-clock *calls* (``time.time()``,
    ``time.perf_counter()``, ``time.perf_counter_ns()``,
    ``time.monotonic()``) outside the sanctioned timing surfaces:
    ``utils/telemetry.py`` (run-report timers + event log),
    ``utils/metrics.py`` (the timed benchmark loop),
    ``utils/profiling.py`` (the host-span recorder: ``annotate`` and the
    package's ``setup/import`` stamp), ``utils/train.py`` (log-window wall
    clock),
    ``utils/resilience.py`` (checkpoint stamps), ``serving/engine.py``
    (serving wall clock), and ``analysis/calibration.py`` (the probe
    harness). Anywhere else, a raw clock read is an ad-hoc step timing
    that bypasses the predicted-vs-measured calibration ledger
    (docs/observability.md §9) — route it through ``utils.metrics``
    so every measurement is reconcilable with the cost model.

The linter is stdlib-only (``ast``) — no jax import, safe for CI legs
that run before any backend exists.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Dict, List, Optional, Set, Tuple

# __init__.py relative imports that may stay eager (see rule docstring).
LAZY_IMPORT_ALLOWLIST = frozenset({"utils.config", "utils.profiling"})

# Calls banned inside tick/scan bodies: (dotted-name, message).
_BANNED_DOTTED = {
    "time.time": "host clock read inside a traced tick/scan body",
    "time.perf_counter": "host clock read inside a traced tick/scan body",
    "np.asarray": "host-side numpy materialization inside a traced "
                  "tick/scan body (use jnp.asarray)",
    "numpy.asarray": "host-side numpy materialization inside a traced "
                     "tick/scan body (use jnp.asarray)",
}

_SCAN_ENTRY_POINTS = {"scan", "fori_loop", "while_loop"}
# positional index of the body callable per entry point
_BODY_ARG_INDEX = {"scan": 0, "fori_loop": 2, "while_loop": 1}


@dataclasses.dataclass(frozen=True)
class LintFinding:
    path: str
    line: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _dotted_name(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _scan_body_names(tree: ast.AST) -> Tuple[Set[str], List[ast.Lambda]]:
    """Names of functions passed as scan/fori/while bodies, plus inline
    lambda bodies."""
    names: Set[str] = set()
    lambdas: List[ast.Lambda] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted_name(node.func)
        if callee is None:
            continue
        leaf = callee.rsplit(".", 1)[-1]
        if leaf not in _SCAN_ENTRY_POINTS:
            continue
        idx = _BODY_ARG_INDEX[leaf]
        if idx < len(node.args):
            body = node.args[idx]
            if isinstance(body, ast.Name):
                names.add(body.id)
            elif isinstance(body, ast.Lambda):
                lambdas.append(body)
    return names, lambdas


def _check_banned_calls(scope: ast.AST, path: str,
                        findings: List[LintFinding]) -> None:
    for node in ast.walk(scope):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted_name(node.func)
        if dotted in _BANNED_DOTTED:
            findings.append(LintFinding(
                path, node.lineno, "scan-body-host-call",
                f"{dotted}(): {_BANNED_DOTTED[dotted]}"))
        elif (isinstance(node.func, ast.Attribute)
              and node.func.attr == "item" and not node.args):
            findings.append(LintFinding(
                path, node.lineno, "scan-body-host-call",
                ".item(): host sync inside a traced tick/scan body"))


def _lint_scan_bodies(tree: ast.AST, path: str,
                      findings: List[LintFinding]) -> None:
    body_names, body_lambdas = _scan_body_names(tree)
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and (
                node.name in body_names or node.name == "tick"):
            _check_banned_calls(node, path, findings)
    for lam in body_lambdas:
        _check_banned_calls(lam, path, findings)


def _lint_init_exports(tree: ast.Module, path: str,
                       findings: List[LintFinding]) -> None:
    for node in tree.body:
        if isinstance(node, ast.ImportFrom) and node.level >= 1:
            module = node.module or ""
            if module in LAZY_IMPORT_ALLOWLIST:
                continue
            findings.append(LintFinding(
                path, node.lineno, "init-lazy-exports",
                f"eager relative import of {'.' * node.level}{module} in "
                f"__init__.py — route re-exports through the _LAZY/"
                f"__getattr__ pattern"))


def _lint_jit_named_scope(tree: ast.AST, path: str,
                          findings: List[LintFinding]) -> None:
    jit_sites: List[int] = []
    has_named_scope = False
    for node in ast.walk(tree):
        dotted = None
        if isinstance(node, ast.Call):
            dotted = _dotted_name(node.func)
        elif isinstance(node, ast.Attribute):
            dotted = _dotted_name(node)
        if dotted == "jax.jit":
            jit_sites.append(node.lineno)
        elif dotted == "jax.named_scope":
            has_named_scope = True
    if not has_named_scope:
        # de-dup Call/Attribute double counting of the same site
        for line in sorted(set(jit_sites)):
            findings.append(LintFinding(
                path, line, "jit-named-scope",
                "jax.jit in parallel/ without any jax.named_scope in the "
                "module — jitted entry points must carry named scopes "
                "for profile attribution"))


# raw-tick-table: files allowed to build/mutate tables directly (the
# compilers and the analysis passes themselves).
_RAW_TABLE_ALLOWLIST = ("parallel/schedules.py", "parallel/native.py")
_TABLE_CTORS = frozenset({"full", "zeros", "ones", "empty"})
_TABLE_NAMESPACES = frozenset({"np", "numpy", "jnp"})


def _mentions_name(node: ast.AST, match) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and match(sub.id):
            return True
        if isinstance(sub, ast.Attribute) and match(sub.attr):
            return True
    return False


def _lint_raw_tables(tree: ast.AST, path: str,
                     findings: List[LintFinding]) -> None:
    is_ncols = lambda s: s in ("N_COLS", "N_COLS_CLASSIC")
    is_col = lambda s: s.startswith("COL_")
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            dotted = _dotted_name(node.func)
            if dotted is not None and "." in dotted:
                ns, leaf = dotted.rsplit(".", 1)
                if (leaf in _TABLE_CTORS
                        and ns.rsplit(".", 1)[-1] in _TABLE_NAMESPACES
                        and any(_mentions_name(a, is_ncols) for a in
                                list(node.args)
                                + [kw.value for kw in node.keywords])):
                    findings.append(LintFinding(
                        path, node.lineno, "raw-tick-table",
                        f"{dotted}(...N_COLS...): raw tick-table "
                        f"construction outside analysis//parallel/"
                        f"schedules.py — go through compile_schedule/"
                        f"compile_order or a certified artifact"))
            # jnp functional update: table.at[..., COL_X].set(v)
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("set", "add", "multiply",
                                           "max", "min")
                    and isinstance(node.func.value, ast.Subscript)
                    and isinstance(node.func.value.value, ast.Attribute)
                    and node.func.value.value.attr == "at"
                    and _mentions_name(node.func.value.slice, is_col)):
                findings.append(LintFinding(
                    path, node.lineno, "raw-tick-table",
                    ".at[...COL_*...] update of a tick-table column "
                    "outside analysis//parallel/schedules.py — compiled "
                    "tables are immutable; go through compile_order or a "
                    "certified artifact"))
            continue
        targets: List[ast.AST] = []
        if isinstance(node, ast.Assign):
            targets = list(node.targets)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        for tgt in targets:
            for sub in ast.walk(tgt):
                if (isinstance(sub, ast.Subscript)
                        and _mentions_name(sub.slice, is_col)):
                    findings.append(LintFinding(
                        path, sub.lineno, "raw-tick-table",
                        "subscript store indexed by a COL_* column "
                        "outside analysis//parallel/schedules.py — "
                        "compiled tables are immutable; go through "
                        "compile_order or a certified artifact"))


# tp-bare-collective: the only functions in parallel/tensor_parallel.py
# allowed to call the bare lax collectives they wrap.
_TP_WRAPPER_FNS = frozenset({"tp_all_gather_matmul",
                             "tp_matmul_reduce_scatter"})
_TP_BARE_COLLECTIVES = frozenset({"all_gather", "psum_scatter"})


def _lint_tp_bare_collectives(tree: ast.AST, path: str,
                              findings: List[LintFinding]) -> None:
    def walk(node: ast.AST, inside_wrapper: bool) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            inside_wrapper = inside_wrapper or node.name in _TP_WRAPPER_FNS
        if isinstance(node, ast.Call) and not inside_wrapper:
            dotted = _dotted_name(node.func)
            if dotted is not None:
                parts = dotted.split(".")
                if (parts[-1] in _TP_BARE_COLLECTIVES
                        and "lax" in parts[:-1]):
                    findings.append(LintFinding(
                        path, node.lineno, "tp-bare-collective",
                        f"{dotted}(): bare collective in parallel/"
                        f"tensor_parallel.py outside the collective-"
                        f"matmul wrappers — route through "
                        f"tp_all_gather_matmul/tp_matmul_reduce_scatter "
                        f"so the tp_overlap knob stays authoritative"))
        for child in ast.iter_child_nodes(node):
            walk(child, inside_wrapper)

    walk(tree, False)


# dynamics-sync-read: modules that own the log-sync boundary (train's
# fit loop, the dynamics host helpers) or read off the timed clock
# (sweep's post-loop probe).
_DYN_SYNC_ALLOWLIST = ("utils/train.py", "utils/dynamics.py",
                       "utils/sweep.py")
# identifiers / dict keys that name device-resident dynamics stats
_DYN_STAT_NAMES = frozenset({
    "sq_mb", "dyn_latest", "dyn_stats",
    "grad_norm_per_stage", "grad_max_per_stage", "nonfinite_per_stage",
    "grad_norm_per_layer", "param_rms_per_stage", "update_ratio_per_stage",
    "last_bad_stage",
})
_SYNC_CALLS = frozenset({"jax.device_get", "jax.block_until_ready"})


def _mentions_dyn_stat(node: ast.AST) -> Optional[str]:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in _DYN_STAT_NAMES:
            return sub.id
        if isinstance(sub, ast.Attribute) and sub.attr in _DYN_STAT_NAMES:
            return sub.attr
        if (isinstance(sub, ast.Constant) and isinstance(sub.value, str)
                and sub.value in _DYN_STAT_NAMES):
            return sub.value
    return None


def _lint_dynamics_sync_reads(tree: ast.AST, path: str,
                              findings: List[LintFinding]) -> None:
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted_name(node.func)
        is_float = isinstance(node.func, ast.Name) and node.func.id == "float"
        if dotted not in _SYNC_CALLS and not is_float:
            continue
        for arg in node.args:
            stat = _mentions_dyn_stat(arg)
            if stat is not None:
                what = dotted if dotted in _SYNC_CALLS else "float"
                findings.append(LintFinding(
                    path, node.lineno, "dynamics-sync-read",
                    f"{what}(...{stat}...): host fetch of a dynamics "
                    f"statistic outside the log-sync boundary "
                    f"(utils/train.py / utils/dynamics.py) — per-stage "
                    f"stats stay device-resident and are read only when "
                    f"the loss syncs (docs/observability.md §7)"))
                break


# raw-step-timing: modules allowed to read host clocks directly — the
# sanctioned timing surfaces plus the calibration probe harness (see
# the rule docstring). Everything else must time through them.
_RAW_TIMING_ALLOWLIST = ("utils/telemetry.py", "utils/metrics.py",
                         "utils/profiling.py", "utils/resilience.py",
                         "utils/train.py", "serving/engine.py",
                         "analysis/calibration.py")
_RAW_TIMING_CALLS = frozenset({"time.time", "time.perf_counter",
                               "time.perf_counter_ns", "time.monotonic"})


def _lint_raw_step_timing(tree: ast.AST, path: str,
                          findings: List[LintFinding]) -> None:
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted_name(node.func)
        if dotted in _RAW_TIMING_CALLS:
            findings.append(LintFinding(
                path, node.lineno, "raw-step-timing",
                f"{dotted}(): raw host-clock read outside the sanctioned "
                f"timing surfaces (utils/metrics.py, utils/telemetry.py, "
                f"...) — ad-hoc step timing bypasses the calibration "
                f"ledger (docs/observability.md §9); route measurements "
                f"through utils.metrics"))


def lint_source(path: str, source: str,
                package_relpath: Optional[str] = None) -> List[LintFinding]:
    """Lint one python source. ``package_relpath`` is the path relative to
    the package root (drives per-directory rules); defaults to ``path``."""
    rel = package_relpath if package_relpath is not None else path
    findings: List[LintFinding] = []
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        findings.append(LintFinding(path, e.lineno or 0, "syntax",
                                    f"unparsable: {e.msg}"))
        return findings
    _lint_scan_bodies(tree, path, findings)
    if os.path.basename(rel) == "__init__.py":
        _lint_init_exports(tree, path, findings)
    parts = rel.replace(os.sep, "/").split("/")
    if "parallel" in parts[:-1]:
        _lint_jit_named_scope(tree, path, findings)
    rel_posix = rel.replace(os.sep, "/")
    if parts[0] != "analysis" and rel_posix not in _RAW_TABLE_ALLOWLIST:
        _lint_raw_tables(tree, path, findings)
    if parts[0] != "analysis" and rel_posix not in _DYN_SYNC_ALLOWLIST:
        _lint_dynamics_sync_reads(tree, path, findings)
    if rel_posix == "parallel/tensor_parallel.py":
        _lint_tp_bare_collectives(tree, path, findings)
    if rel_posix not in _RAW_TIMING_ALLOWLIST:
        _lint_raw_step_timing(tree, path, findings)
    return findings


def package_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_repo(root: Optional[str] = None) -> List[LintFinding]:
    """Lint every ``.py`` file under the package (default: this package's
    own root). Returns findings sorted by (path, line)."""
    root = root or package_root()
    findings: List[LintFinding] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", ".git", "build")]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, root)
            with open(path, "r", encoding="utf-8") as fh:
                src = fh.read()
            findings.extend(lint_source(path, src, package_relpath=rel))
    findings.sort(key=lambda f: (f.path, f.line))
    return findings


def findings_summary(findings: List[LintFinding]) -> Dict[str, object]:
    by_rule: Dict[str, int] = {}
    for f in findings:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    return {"n_findings": len(findings), "by_rule": by_rule,
            "findings": [str(f) for f in findings]}
