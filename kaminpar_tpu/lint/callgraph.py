"""tpulint call graph: intra-package def/import resolution and
one-level helper hazard summaries.

The v1 engine was deliberately module-local: span-scope analysis (R1)
only saw hazards written *lexically* inside a ``with scoped_timer``
block, so factoring a host pull into a helper silently passed the
check — the loophole every "hook shape" fixture leaned on.  This module
closes it one level deep:

  * :class:`PackageIndex` parses every linted file once and records, per
    module, its top-level functions, its class methods, and an import
    map that resolves *relative* imports (``from ..telemetry import
    quality``) against the module's dotted name — the package's actual
    import idiom, which the v1 alias map skipped;
  * :func:`PackageIndex.resolve` maps a call expression (``helper(..)``,
    ``mod.helper(..)``, ``self.method(..)``) to the function definition
    it names, same-module or cross-module;
  * :func:`PackageIndex.summary` extracts a :class:`HelperSummary` of
    the hazards written directly in that function's body — host-sync
    primitives, device/backend queries, perf introspections, SPMD
    collectives, fault-surface entries, rank reads.

Rules consult the summary at the call site: a call inside a span scope
to a helper whose body host-syncs is the same distortion as the inline
pull, and is reported at the call site (where the fix belongs).

Known blind spots, by design (documented in docs/static_analysis.md):
inlining is ONE level (a pull two calls deep stays invisible — the
baseline ratchet's job, not the linter's); resolution is name-based
(no dataflow: a helper passed as a callback is not followed); and
suppression comments in the *helper's* file are honored, so a helper
whose hazard line carries a justified ``# tpulint: disable=`` never
taints its callers.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

# ---------------------------------------------------------------------------
# shared hazard surfaces (rules.py / spmd.py import these; this module
# is the bottom layer and imports nothing from the rest of the linter)

#: R2: the device/backend discovery surface that must stay behind the
#: utils.platform gate (eager discovery initializes every registered
#: plug-in despite JAX_PLATFORMS=cpu; one such init hung test_capi 600 s).
DEVICE_QUERIES = frozenset(
    {
        "jax.devices",
        "jax.local_devices",
        "jax.device_count",
        "jax.local_device_count",
        "jax.default_backend",
        "jax.process_index",
        "jax.process_count",
        "jax.lib.xla_bridge.get_backend",
        "jax.extend.backend.get_backend",
    }
)

#: R6: eager memory/cost introspection (see rules.py for the rule text).
R6_QUERIES = frozenset(
    {
        "jax.live_arrays",
        "jax.profiler.device_memory_profile",
    }
)
R6_METHODS = frozenset(
    {
        "cost_analysis",
        "memory_analysis",
        "get_compiled_memory_stats",
        "device_memory_profile",
    }
)

#: R7: calls every rank of an SPMD fleet must reach together — a rank
#: that skips one deadlocks the survivors inside the collective (the
#: static half of the PR-12 divergence sentinel).  Terminal names, so
#: `lax.psum`, `mesh.halo_exchange` and bare `psum` all match.
COLLECTIVE_CALLS = frozenset(
    {
        "psum",
        "psum_scatter",
        "pmean",
        "pmax",
        "pmin",
        "all_gather",
        "allgather",
        "all_to_all",
        "ppermute",
        "pshuffle",
        "shard_map",
        "agree_max",
        "agree_min",
        "agree_sum",
        "gather_i64",
        "process_allgather",
        "halo_exchange",
        "sync_global_devices",
        "broadcast_one_to_all",
    }
)

#: R7: expressions whose value differs per rank — control flow branching
#: on one of these in front of a collective is the divergence hazard.
RANK_SOURCE_CALLS = frozenset(
    {
        "rank",
        "process_index",
        "local_rank",
        "is_primary_process",
        "is_primary",
    }
)
RANK_SOURCE_QUALNAMES = frozenset(
    {
        "jax.process_index",
    }
)
_RANK_ENV_RE = re.compile(r"RANK", re.IGNORECASE)

#: R8: entry points of the degradation/fault contract
#: (resilience/policy.py, resilience/faults.py).  A broad handler
#: swallowing exceptions around one of these defeats the classification
#: the contract exists to enforce.
FAULT_SURFACE_CALLS = frozenset(
    {
        "with_fallback",
        "maybe_inject",
    }
)

_SUPPRESS_RE = re.compile(
    r"#\s*tpulint:\s*(disable|disable-file)\s*=\s*"
    r"([A-Za-z0-9]+(?:\s*,\s*[A-Za-z0-9]+)*)"
)


def collect_aliases(tree: ast.Module) -> Dict[str, str]:
    """Absolute-import alias map (``jnp`` -> ``jax.numpy``); the same
    map the v1 engine built, shared here so summaries resolve qualnames
    identically to the lexical rules."""
    aliases: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                aliases[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0]
                )
        elif isinstance(node, ast.ImportFrom) and node.module and not node.level:
            for a in node.names:
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def qualname_in(node: ast.AST, aliases: Dict[str, str]) -> Optional[str]:
    """Dotted name of a Name/Attribute chain with aliases resolved."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(aliases.get(node.id, node.id))
    return ".".join(reversed(parts))


def terminal_name(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def module_name_for(path: str) -> str:
    """Dotted module name for a repo-relative posix path; files outside
    a package tree (fixtures, snippets) get their bare stem."""
    p = path.replace("\\", "/")
    if p.endswith(".py"):
        p = p[: -len(".py")]
    if p.endswith("/__init__"):
        p = p[: -len("/__init__")]
    parts = p.split("/")
    if "kaminpar_tpu" in parts:
        parts = parts[parts.index("kaminpar_tpu"):]
        return ".".join(parts)
    return parts[-1]


@dataclass
class HelperSummary:
    """Hazards written directly in one function's body (nested defs
    excluded: closures run at their own call sites, not this one)."""

    host_syncs: List[Tuple[int, str]] = field(default_factory=list)
    device_queries: List[Tuple[int, str]] = field(default_factory=list)
    perf_introspections: List[Tuple[int, str]] = field(default_factory=list)
    collectives: List[Tuple[int, str]] = field(default_factory=list)
    fault_surface: List[Tuple[int, str]] = field(default_factory=list)
    rank_dependent: bool = False


@dataclass
class FunctionInfo:
    name: str
    qualname: str  # module.func or module.Class.func
    node: ast.AST
    module: "ModuleInfo"


class ModuleInfo:
    """One parsed module as the call graph sees it."""

    def __init__(self, path: str, source: str, tree: ast.Module) -> None:
        self.path = path
        self.name = module_name_for(path)
        self.tree = tree
        self.aliases = collect_aliases(tree)
        self.suppressed_lines = _suppressed_lines(source)
        # top-level defs and class methods (one level of class nesting —
        # the package's layout; deeper nesting is a blind spot)
        self.functions: Dict[str, FunctionInfo] = {}
        self.methods: Dict[str, Dict[str, FunctionInfo]] = {}
        for node in tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions[node.name] = FunctionInfo(
                    node.name, f"{self.name}.{node.name}", node, self
                )
            elif isinstance(node, ast.ClassDef):
                table: Dict[str, FunctionInfo] = {}
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        table[sub.name] = FunctionInfo(
                            sub.name,
                            f"{self.name}.{node.name}.{sub.name}",
                            sub, self,
                        )
                self.methods[node.name] = table
        # import map including RELATIVE imports resolved against this
        # module's dotted name: local name -> dotted target
        self.imports: Dict[str, str] = dict(self.aliases)
        pkg_parts = self.name.split(".")[:-1]
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.level:
                # `from ..x import y` with level=2 strips one extra part
                base = pkg_parts[: len(pkg_parts) - (node.level - 1)]
                mod = ".".join(base + (node.module or "").split("."))
                mod = mod.strip(".")
                for a in node.names:
                    self.imports[a.asname or a.name] = (
                        f"{mod}.{a.name}" if mod else a.name
                    )


def _suppressed_lines(source: str) -> Dict[int, Set[str]]:
    """Per-line suppressed rule sets, with the comment-line-above
    convention (mirrors engine._parse_suppressions; file-wide
    suppressions are folded in by the caller via line 0)."""
    per_line: Dict[int, Set[str]] = {}
    lines = source.splitlines()
    for lineno, line in enumerate(lines, start=1):
        m = _SUPPRESS_RE.search(line)
        if not m:
            continue
        kind, rules = m.groups()
        names = {r.strip().upper() for r in rules.split(",") if r.strip()}
        if kind == "disable-file":
            per_line.setdefault(0, set()).update(names)
            continue
        target = lineno
        if line.lstrip().startswith("#"):
            nxt = lineno + 1
            while nxt <= len(lines) and lines[nxt - 1].lstrip().startswith("#"):
                nxt += 1
            target = nxt
        per_line.setdefault(target, set()).update(names)
    return per_line


def _mentions_jax(node: ast.AST, aliases: Dict[str, str]) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, (ast.Name, ast.Attribute)):
            q = qualname_in(sub, aliases)
            if q and (q == "jax" or q.startswith("jax.")):
                return True
    return False


def _own_body_nodes(fn: ast.AST):
    """Walk a function's own statements, pruning nested function/lambda
    bodies (those hazards belong to the closure's call sites)."""
    work = list(getattr(fn, "body", []))
    while work:
        node = work.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        yield node
        work.extend(ast.iter_child_nodes(node))


def _is_env_rank_read(node: ast.Call, aliases: Dict[str, str]) -> bool:
    q = qualname_in(node.func, aliases)
    if q not in ("os.environ.get", "os.getenv"):
        return False
    return any(
        isinstance(a, ast.Constant) and isinstance(a.value, str)
        and _RANK_ENV_RE.search(a.value)
        for a in node.args
    )


class PackageIndex:
    """Cross-module def/import resolution over one lint invocation."""

    def __init__(self) -> None:
        self.by_name: Dict[str, ModuleInfo] = {}
        self.by_path: Dict[str, ModuleInfo] = {}
        self._summaries: Dict[int, HelperSummary] = {}

    def add(self, path: str, source: str, tree: ast.Module) -> ModuleInfo:
        info = ModuleInfo(path, source, tree)
        self.by_name[info.name] = info
        self.by_path[path] = info
        return info

    # -- resolution --------------------------------------------------------

    def resolve(self, module: ModuleInfo, call: ast.Call,
                enclosing_class: Optional[str] = None
                ) -> Optional[FunctionInfo]:
        """The function definition a call names, or None.  Handles
        ``helper()``, ``imported_helper()``, ``mod.helper()`` and
        ``self.method()`` / ``cls.method()`` (within the lexically
        enclosing class)."""
        func = call.func
        if isinstance(func, ast.Name):
            local = module.functions.get(func.id)
            if local is not None:
                return local
            target = module.imports.get(func.id)
            if target:
                return self._lookup_dotted(target)
            return None
        if isinstance(func, ast.Attribute):
            base = func.value
            if isinstance(base, ast.Name):
                if base.id in ("self", "cls") and enclosing_class:
                    table = module.methods.get(enclosing_class, {})
                    return table.get(func.attr)
                target = module.imports.get(base.id)
                if target:
                    mod = self.by_name.get(target)
                    if mod is not None:
                        return mod.functions.get(func.attr)
                    return self._lookup_dotted(f"{target}.{func.attr}")
        return None

    def _lookup_dotted(self, dotted: str) -> Optional[FunctionInfo]:
        mod_name, _, fn_name = dotted.rpartition(".")
        if not mod_name:
            return None
        mod = self.by_name.get(mod_name)
        if mod is not None:
            return mod.functions.get(fn_name)
        return None

    # -- summaries ---------------------------------------------------------

    def summary(self, fn: FunctionInfo) -> HelperSummary:
        cached = self._summaries.get(id(fn.node))
        if cached is not None:
            return cached
        s = self._summarize(fn)
        self._summaries[id(fn.node)] = s
        return s

    def _summarize(self, fn: FunctionInfo) -> HelperSummary:
        mod = fn.module
        aliases = mod.aliases
        s = HelperSummary()
        file_wide = mod.suppressed_lines.get(0, set())
        # a suppression ON (or commented above) the `def` line declares
        # the helper as a HOST-BOUNDARY function for that rule: its
        # hazards are its contract, so nothing is summarized and every
        # call site stays clean at once — one justified declaration at
        # the def instead of one suppression per sync line
        def_wide = mod.suppressed_lines.get(
            getattr(fn.node, "lineno", 0), set()
        )

        def allowed(rule: str, line: int) -> bool:
            if "ALL" in file_wide or rule in file_wide:
                return False
            if "ALL" in def_wide or rule in def_wide:
                return False
            at = mod.suppressed_lines.get(line, set())
            return not ("ALL" in at or rule in at)

        for node in _own_body_nodes(fn.node):
            if not isinstance(node, ast.Call):
                continue
            line = getattr(node, "lineno", 0)
            q = qualname_in(node.func, aliases)
            name = terminal_name(node.func)

            # R1-class host syncs (mirrors rules.py R1a/b/c exactly)
            if allowed("R1", line):
                if (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item"
                    and not node.args
                ):
                    s.host_syncs.append((line, ".item()"))
                elif (
                    isinstance(node.func, ast.Name)
                    and node.func.id in ("int", "float", "bool")
                    and node.func.id not in aliases
                    and node.args
                    and _mentions_jax(node.args[0], aliases)
                ):
                    s.host_syncs.append(
                        (line, f"{node.func.id}() of a jax value")
                    )
                elif (
                    q in ("numpy.asarray", "numpy.array")
                    and node.args
                    and not isinstance(
                        node.args[0], (ast.List, ast.Tuple, ast.Constant)
                    )
                ):
                    s.host_syncs.append((line, f"{q}()"))

            if q in DEVICE_QUERIES and allowed("R2", line):
                s.device_queries.append((line, f"{q}()"))

            if allowed("R6", line):
                if q in R6_QUERIES:
                    s.perf_introspections.append((line, f"{q}()"))
                elif (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in R6_METHODS
                ):
                    s.perf_introspections.append(
                        (line, f".{node.func.attr}()")
                    )

            if name in COLLECTIVE_CALLS and allowed("R7", line):
                s.collectives.append((line, f"{name}()"))

            if allowed("R8", line):
                if name in FAULT_SURFACE_CALLS or any(
                    kw.arg == "site" for kw in node.keywords
                ):
                    s.fault_surface.append(
                        (line, f"{name or '<call>'}()")
                    )

            if (
                name in RANK_SOURCE_CALLS
                or q in RANK_SOURCE_QUALNAMES
                or _is_env_rank_read(node, aliases)
            ):
                s.rank_dependent = True
        return s
