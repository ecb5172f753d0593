"""tpulint core: AST analysis, suppressions, file walking.

One analyzer instance handles one module.  The rule logic lives in
``rules.py`` (R1-R4, R6) and ``spmd.py`` (R7/R8); ``schema_pins.py`` owns
the cross-file R9 check and ``callgraph.py`` the package index.  This
module owns the shared machinery every rule needs:

  * import alias resolution (``jnp`` -> ``jax.numpy``) so rules match
    fully-qualified names regardless of local import style;
  * the module-local jit call graph (which functions are
    ``jax.jit``-decorated or transitively called from one) for R1;
  * the cross-module :class:`callgraph.PackageIndex` (one-level helper
    inlining) so span-scope analysis follows factored helpers;
  * lexical context stacks (function nesting, loop depth, span-scope
    ``with`` blocks) maintained during a single AST walk;
  * ``# tpulint: disable=``/``disable-file=`` suppression parsing.

Per-module analysis stays deterministic and dependency-free; the call
graph adds exactly one level of inlining (a pull two calls deep is a
documented blind spot, docs/static_analysis.md#call-graph).
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from . import callgraph as cg

RULES: Dict[str, str] = {
    "R1": "host-sync primitive in jit-reachable code or a span scope "
          "(lexically or one helper call deep)",
    "R2": "eager/ungated device or backend query (use utils.platform)",
    "R3": "32-bit accumulation where the dtypes.py 64-bit policy applies",
    "R4": "jit wrapper constructed per iteration/evaluation (retrace)",
    "R6": "eager device-memory/cost introspection outside the gated "
          "perf helpers (telemetry.perf / utils.heap_profiler)",
    "R7": "rank-dependent control flow guarding an SPMD collective "
          "(the static half of the divergence sentinel)",
    "R8": "broad except around the degradation/fault surface without "
          "routing through with_fallback/classify",
    "R9": "run-report schema-version pin skew across producer/schema/"
          "checker/fixtures (cross-file)",
}

_SUPPRESS_RE = re.compile(
    r"#\s*tpulint:\s*(disable|disable-file)\s*=\s*"
    r"([A-Za-z0-9]+(?:\s*,\s*[A-Za-z0-9]+)*)"
)


@dataclass(frozen=True)
class Finding:
    path: str  # repo-relative, posix separators
    rule: str
    line: int
    col: int
    symbol: str  # enclosing function ('<module>' at top level)
    message: str
    code: str  # stripped source line, the churn-stable baseline key

    def render(self) -> str:
        return (
            f"{self.path}:{self.line}:{self.col}: {self.rule} "
            f"[{self.symbol}] {self.message}"
        )

    def to_dict(self) -> dict:
        return {
            "path": self.path,
            "rule": self.rule,
            "line": self.line,
            "col": self.col,
            "symbol": self.symbol,
            "message": self.message,
            "code": self.code,
        }


@dataclass
class LintConfig:
    """Knobs the CLI and tests tune; defaults match the package layout."""

    # files allowed to call jax device/backend queries directly (the gate)
    gate_suffixes: Tuple[str, ...] = ("utils/platform.py",)
    # files allowed to walk live arrays / cost-analyze executables /
    # profile device memory directly (R6's gate: the perf observatory
    # and the heap profiler own those probes behind enabled() checks)
    perf_gate_suffixes: Tuple[str, ...] = (
        "telemetry/perf.py",
        "utils/heap_profiler.py",
    )
    # R3 fires only under these directory names (plus lint fixtures)
    r3_dirs: Tuple[str, ...] = ("ops", "graphs", "parallel", "lint_fixtures")
    # R7: the deliberate rank-0-writes idiom — checkpointing and report
    # emission are DOCUMENTED single-writer surfaces (every rank agrees
    # on the data first, rank 0 alone touches the filesystem), and the
    # agreement layer itself implements the collectives it guards
    r7_allow_suffixes: Tuple[str, ...] = (
        "resilience/checkpoint.py",
        "resilience/agreement.py",
        "telemetry/report.py",
    )
    # R8: legitimate broad-except boundaries — processes/layers whose
    # CONTRACT is "never let any exception cross" (serving isolation
    # marshals verdicts, the supervisor marshals worker death, telemetry
    # is best-effort by design).  Substring match on the posix path.
    r8_boundary_parts: Tuple[str, ...] = (
        "serving/service.py",
        "resilience/supervisor.py",
        "telemetry/",
    )
    # R9: the four schema-version pin sites (relative to r9_root; None
    # root = the repo that holds this package)
    r9_root: Optional[str] = None
    r9_producer_rel: str = "kaminpar_tpu/telemetry/report.py"
    r9_schema_rel: str = "kaminpar_tpu/telemetry/run_report.schema.json"
    r9_checker_rel: str = "scripts/check_report_schema.py"
    # rules to run (all by default)
    rules: Tuple[str, ...] = tuple(RULES)


def _parse_suppressions(source: str) -> Tuple[Dict[int, Set[str]], Set[str]]:
    """(per-line rule sets, file-wide rule set); 'all' disables everything.

    A ``# tpulint: disable=`` on a comment-only line applies to the next
    code line (so long statements can carry their justification above)."""
    per_line: Dict[int, Set[str]] = {}
    per_file: Set[str] = set()
    lines = source.splitlines()
    for lineno, line in enumerate(lines, start=1):
        m = _SUPPRESS_RE.search(line)
        if not m:
            continue
        kind, rules = m.groups()
        names = {r.strip().upper() for r in rules.split(",") if r.strip()}
        if kind == "disable-file":
            per_file |= names
            continue
        target = lineno
        if line.lstrip().startswith("#"):
            # comment-only line: attach to the next code line
            nxt = lineno + 1
            while nxt <= len(lines) and lines[nxt - 1].lstrip().startswith("#"):
                nxt += 1
            target = nxt
        per_line.setdefault(target, set()).update(names)
    return per_line, per_file


class ModuleContext:
    """Everything rules need to know about one parsed module."""

    def __init__(self, path: str, source: str, tree: ast.Module,
                 config: LintConfig,
                 index: Optional[cg.PackageIndex] = None) -> None:
        self.path = path
        self.source_lines = source.splitlines()
        self.tree = tree
        self.config = config
        self.aliases = _collect_aliases(tree)
        self.jit_reachable = _jit_reachable_functions(tree, self)
        self.is_gate_module = any(
            path.endswith(sfx) for sfx in config.gate_suffixes
        )
        self.is_perf_gate_module = any(
            path.endswith(sfx) for sfx in config.perf_gate_suffixes
        )
        parts = set(path.replace("\\", "/").split("/"))
        self.r3_applies = bool(parts & set(config.r3_dirs))
        # cross-module call graph; a single-module index is built on the
        # fly so same-file helpers resolve even in snippet/fixture runs
        if index is None:
            index = cg.PackageIndex()
            index.add(path, source, tree)
        self.index = index
        self.module_info = index.by_path.get(path)

    def resolve_call(self, node: ast.Call,
                     enclosing_class: Optional[str] = None
                     ) -> Optional[cg.FunctionInfo]:
        """The package-defined function a call names (same or cross
        module, ``self.method`` within the enclosing class), else None."""
        if self.module_info is None:
            return None
        return self.index.resolve(self.module_info, node, enclosing_class)

    def helper_summary(self, fn: cg.FunctionInfo) -> cg.HelperSummary:
        return self.index.summary(fn)

    def qualname(self, node: ast.AST) -> Optional[str]:
        """Dotted name of a Name/Attribute chain with aliases resolved;
        None for anything that is not a plain chain."""
        parts: List[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        root = self.aliases.get(node.id, node.id)
        parts.append(root)
        return ".".join(reversed(parts))

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.source_lines):
            return self.source_lines[lineno - 1].strip()
        return ""


_collect_aliases = cg.collect_aliases


_JIT_WRAPPERS = ("jax.jit", "jax.pmap")


def _is_jit_decorator(dec: ast.AST, ctx: "ModuleContext") -> bool:
    """@jax.jit, @jit (from jax), @functools.partial(jax.jit, ...),
    @jax.jit(...) — anything that makes the function a trace root."""
    q = ctx.qualname(dec)
    if q in _JIT_WRAPPERS:
        return True
    if isinstance(dec, ast.Call):
        fq = ctx.qualname(dec.func)
        if fq in _JIT_WRAPPERS:
            return True
        if fq in ("functools.partial", "partial") and dec.args:
            return ctx.qualname(dec.args[0]) in _JIT_WRAPPERS
    return False


def _jit_reachable_functions(tree: ast.Module, ctx: "ModuleContext"
                             ) -> Set[ast.AST]:
    """Function nodes that are jit roots or transitively called from one
    (module-local, by simple name).  Nested defs inherit reachability
    from their enclosing function."""
    funcs: List[ast.AST] = [
        n for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]
    by_name: Dict[str, List[ast.AST]] = {}
    for f in funcs:
        by_name.setdefault(f.name, []).append(f)

    parent: Dict[ast.AST, ast.AST] = {}
    for f in funcs:
        for inner in ast.walk(f):
            if inner is not f and isinstance(
                inner, (ast.FunctionDef, ast.AsyncFunctionDef)
            ) and inner not in parent:
                parent[inner] = f

    roots: Set[ast.AST] = {
        f for f in funcs
        if any(_is_jit_decorator(d, ctx) for d in f.decorator_list)
    }
    # module-level `g = jax.jit(f)` marks f as a root
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and ctx.qualname(node.func) in _JIT_WRAPPERS:
            for arg in node.args[:1]:
                if isinstance(arg, ast.Name):
                    roots.update(by_name.get(arg.id, []))

    calls: Dict[ast.AST, Set[str]] = {}
    for f in funcs:
        names: Set[str] = set()
        for inner in ast.walk(f):
            if isinstance(inner, ast.Call) and isinstance(inner.func, ast.Name):
                names.add(inner.func.id)
        calls[f] = names

    reachable: Set[ast.AST] = set()
    work = list(roots)
    while work:
        f = work.pop()
        if f in reachable:
            continue
        reachable.add(f)
        for name in calls.get(f, ()):
            for g in by_name.get(name, []):
                if g not in reachable:
                    work.append(g)
    # nested defs of reachable functions trace with them
    changed = True
    while changed:
        changed = False
        for child, par in parent.items():
            if par in reachable and child not in reachable:
                reachable.add(child)
                work.append(child)
                changed = True
        while work:
            f = work.pop()
            for name in calls.get(f, ()):
                for g in by_name.get(name, []):
                    if g not in reachable:
                        reachable.add(g)
                        work.append(g)
                        changed = True
    return reachable


def _repo_relative(path: str) -> str:
    """Stable posix-style path for findings/baselines: relative to the
    repo root (the directory holding the kaminpar_tpu package) when the
    file is under it, else relative to cwd, else absolute."""
    ap = os.path.abspath(path)
    pkg_root = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    repo_root = os.path.dirname(pkg_root)
    for base in (repo_root, os.getcwd()):
        if ap.startswith(base.rstrip(os.sep) + os.sep):
            return os.path.relpath(ap, base).replace(os.sep, "/")
    return ap.replace(os.sep, "/")


def lint_source(source: str, path: str,
                config: Optional[LintConfig] = None,
                index: Optional[cg.PackageIndex] = None) -> List[Finding]:
    """Lint one module's source text (path is used for reporting and
    path-scoped rules only; without an explicit package index a
    single-module one is built so same-file helpers still resolve)."""
    from . import rules as rules_mod
    from . import spmd as spmd_mod

    config = config or LintConfig()
    rel = _repo_relative(path)
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [
            Finding(
                path=rel, rule="E0", line=int(e.lineno or 0), col=0,
                symbol="<module>",
                message=f"syntax error: {e.msg}",
                code="",
            )
        ]
    ctx = ModuleContext(rel, source, tree, config, index=index)
    per_line, per_file = _parse_suppressions(source)

    raw = rules_mod.run_rules(ctx) + spmd_mod.run_spmd_rules(ctx)
    findings: List[Finding] = []
    for f in raw:
        # E0 (syntax error) always passes the rule filter
        if f.rule not in config.rules and f.rule != "E0":
            continue
        if "ALL" in per_file or f.rule in per_file:
            continue
        line_rules = per_line.get(f.line, set())
        if "ALL" in line_rules or f.rule in line_rules:
            continue
        findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings


def lint_file(path: str, config: Optional[LintConfig] = None,
              index: Optional[cg.PackageIndex] = None) -> List[Finding]:
    with open(path, encoding="utf-8") as fh:
        return lint_source(fh.read(), path, config, index=index)


def _iter_py_files(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(
                    d for d in dirs
                    if d not in ("__pycache__", ".git")
                )
                for name in sorted(files):
                    if name.endswith(".py"):
                        out.append(os.path.join(root, name))
        elif p.endswith(".py"):
            out.append(p)
    return out


def lint_paths(paths: Sequence[str],
               config: Optional[LintConfig] = None) -> List[Finding]:
    """Lint every .py file under the given paths (files or directories).

    Two passes: the first parses every file into one PackageIndex (the
    cross-module call graph), the second runs the rules with that index
    so span/guard analysis follows helpers across files.  When R9 is
    selected the cross-file schema-pin check runs once per invocation
    on top (it reads the repo's pin sites, not the linted paths)."""
    config = config or LintConfig()
    index = cg.PackageIndex()
    sources: List[Tuple[str, str]] = []
    for path in _iter_py_files(paths):
        try:
            with open(path, encoding="utf-8") as fh:
                source = fh.read()
        except OSError:
            continue
        sources.append((path, source))
        try:
            tree = ast.parse(source, filename=path)
        except SyntaxError:
            continue  # lint_source re-parses and reports E0
        index.add(_repo_relative(path), source, tree)

    findings: List[Finding] = []
    for path, source in sources:
        findings.extend(lint_source(source, path, config, index=index))
    if "R9" in config.rules:
        from . import schema_pins

        findings.extend(schema_pins.check_schema_pins(config))
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings
