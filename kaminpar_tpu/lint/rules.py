"""tpulint rule implementations (R1-R4, R6).

Each rule documents the incident that motivated it (VERDICT/ADVICE round
5) next to the pattern it matches; docs/static_analysis.md is the
operator-facing version.  All rules run in one AST walk that maintains
the lexical context stacks (enclosing function, loop depth, telemetry
span scopes).
"""

from __future__ import annotations

import ast
from typing import List, Optional

from .callgraph import (  # shared hazard surfaces (bottom layer)
    DEVICE_QUERIES,
    R6_METHODS,
    R6_QUERIES,
)
from .engine import Finding, ModuleContext, _is_jit_decorator

# R3: reductions whose accumulator width the dtypes.py policy owns.
ACC_CALLS = frozenset(
    {"cumsum", "sum", "segment_sum", "bincount", "prod", "dot", "einsum"}
)
INT32_NAMES = frozenset({"jax.numpy.int32", "numpy.int32"})


def _terminal_name(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _mentions_jax(node: ast.AST, ctx: ModuleContext) -> bool:
    """True when the subtree references anything under the jax package."""
    for sub in ast.walk(node):
        if isinstance(sub, (ast.Name, ast.Attribute)):
            q = ctx.qualname(sub)
            if q and (q == "jax" or q.startswith("jax.")):
                return True
    return False


def _is_int32(node: ast.AST, ctx: ModuleContext) -> bool:
    q = ctx.qualname(node)
    if q in INT32_NAMES:
        return True
    return isinstance(node, ast.Constant) and node.value == "int32"


def _is_span_scope_item(item: ast.withitem, ctx: ModuleContext) -> bool:
    """`with scoped_timer(...)` / `with <timer>.scope(...)` — a telemetry
    span scope.  Scopes that declare sync= measure their own host sync
    and are exempt from R1."""
    call = item.context_expr
    if not isinstance(call, ast.Call):
        return False
    name = _terminal_name(call.func)
    if name not in ("scoped_timer", "scope"):
        return False
    return not any(kw.arg == "sync" for kw in call.keywords)


class _RuleWalker(ast.NodeVisitor):
    def __init__(self, ctx: ModuleContext) -> None:
        self.ctx = ctx
        self.findings: List[Finding] = []
        self.func_stack: List[ast.AST] = []
        self.class_stack: List[str] = []
        self.loop_depth = 0
        self.span_depth = 0

    # -- helpers ----------------------------------------------------------

    def _symbol(self) -> str:
        if self.func_stack:
            return ".".join(
                f.name for f in self.func_stack
                if isinstance(f, (ast.FunctionDef, ast.AsyncFunctionDef))
            )
        return "<module>"

    def _emit(self, rule: str, node: ast.AST, message: str) -> None:
        line = getattr(node, "lineno", 0)
        self.findings.append(
            Finding(
                path=self.ctx.path,
                rule=rule,
                line=line,
                col=getattr(node, "col_offset", 0),
                symbol=self._symbol(),
                message=message,
                code=self.ctx.line_text(line),
            )
        )

    def _in_jit(self) -> bool:
        return bool(
            self.func_stack
            and self.func_stack[-1] in self.ctx.jit_reachable
        )

    def _r1_scope(self) -> Optional[str]:
        """Why R1 applies here (None when it does not)."""
        if self._in_jit():
            return "jit-reachable code"
        if self.span_depth > 0:
            return "a telemetry span scope"
        return None

    # -- structure visitors ------------------------------------------------

    def _visit_function(self, node) -> None:
        # R4: a jit-decorated def inside a loop mints a fresh traced
        # callable per iteration — the jit cache keys on function
        # identity, so every iteration recompiles.
        if self.loop_depth and any(
            _is_jit_decorator(d, self.ctx) for d in node.decorator_list
        ):
            self._emit(
                "R4", node,
                f"jit-decorated function '{node.name}' defined inside a "
                "loop retraces every iteration; hoist the definition",
            )
        self.func_stack.append(node)
        self.generic_visit(node)
        self.func_stack.pop()

    visit_FunctionDef = _visit_function
    visit_AsyncFunctionDef = _visit_function

    def visit_Lambda(self, node: ast.Lambda) -> None:
        # a lambda is a deferred thunk: the checkpoint barrier's
        # `payload=` and dist_lp's `materialize=` hooks run it outside
        # the hot path (or never), so its body is not part of the
        # enclosing span.  A lambda invoked in place escapes — a
        # documented blind spot (docs/static_analysis.md#call-graph).
        saved = self.span_depth
        self.span_depth = 0
        self.generic_visit(node)
        self.span_depth = saved

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.class_stack.append(node.name)
        self.generic_visit(node)
        self.class_stack.pop()

    def visit_With(self, node: ast.With) -> None:
        spans = sum(
            1 for item in node.items if _is_span_scope_item(item, self.ctx)
        )
        for item in node.items:
            self.visit(item)
        self.span_depth += 1 if spans else 0
        for stmt in node.body:
            self.visit(stmt)
        self.span_depth -= 1 if spans else 0

    def _visit_loop(self, node) -> None:
        # loop headers (iter/test) are visited at the current depth
        for fname, value in ast.iter_fields(node):
            if fname in ("body", "orelse"):
                continue
            if isinstance(value, ast.AST):
                self.visit(value)
            elif isinstance(value, list):
                for v in value:
                    if isinstance(v, ast.AST):
                        self.visit(v)
        self.loop_depth += 1
        for stmt in list(node.body) + list(node.orelse):
            self.visit(stmt)
        self.loop_depth -= 1

    visit_For = _visit_loop
    visit_AsyncFor = _visit_loop

    def visit_While(self, node: ast.While) -> None:
        self._check_branch_on_tracer(node, "while")
        self._visit_loop(node)

    def visit_If(self, node: ast.If) -> None:
        self._check_branch_on_tracer(node, "if")
        self.generic_visit(node)

    def _check_branch_on_tracer(self, node, kw: str) -> None:
        scope = self._r1_scope()
        if scope is None or not self._in_jit():
            # span scopes run un-traced python; branching there is fine
            return
        test = node.test
        for sub in ast.walk(test):
            if isinstance(sub, ast.Call) and _mentions_jax(sub.func, self.ctx):
                self._emit(
                    "R1", node,
                    f"python `{kw}` on a traced jax expression inside "
                    f"{scope}: forces a host sync (or a trace error); "
                    "use lax.cond/jnp.where",
                )
                return

    # -- call-site rules ---------------------------------------------------

    def visit_Call(self, node: ast.Call) -> None:
        ctx = self.ctx
        q = ctx.qualname(node.func)
        scope = self._r1_scope()

        # R1a: .item() is an unconditional device->host sync
        if (
            scope is not None
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "item"
            and not node.args
        ):
            self._emit(
                "R1", node,
                f".item() inside {scope} blocks on the device; hoist the "
                "readback out of the hot path",
            )

        # R1b: int()/float()/bool() of a jax expression
        if (
            scope is not None
            and isinstance(node.func, ast.Name)
            and node.func.id in ("int", "float", "bool")
            and node.func.id not in ctx.aliases
            and node.args
            and _mentions_jax(node.args[0], ctx)
        ):
            self._emit(
                "R1", node,
                f"{node.func.id}() of a jax value inside {scope} "
                "host-syncs; keep the value on device or hoist the "
                "readback",
            )

        # R1c: np.asarray/np.array of a non-literal inside a hot scope
        if (
            scope is not None
            and q in ("numpy.asarray", "numpy.array")
            and node.args
            and not isinstance(
                node.args[0], (ast.List, ast.Tuple, ast.Constant)
            )
        ):
            self._emit(
                "R1", node,
                f"{q}() inside {scope} copies device data to host "
                "synchronously; stage the transfer outside the scope",
            )

        # R2: device/backend discovery outside the lazy gate
        if q in DEVICE_QUERIES and not ctx.is_gate_module:
            if not self.func_stack:
                self._emit(
                    "R2", node,
                    f"{q}() at import time eagerly initializes backends "
                    "(the test_capi 600 s hang class); defer it into a "
                    "function and route through kaminpar_tpu.utils.platform",
                )
            else:
                self._emit(
                    "R2", node,
                    f"direct {q}() bypasses the JAX_PLATFORMS-respecting "
                    "gate; use kaminpar_tpu.utils.platform instead",
                )

        # R3: int32-accumulating reductions on the 64-bit policy path
        if ctx.r3_applies:
            name = _terminal_name(node.func)
            if name in ACC_CALLS:
                for kw in node.keywords:
                    if kw.arg == "dtype" and _is_int32(kw.value, ctx):
                        self._emit(
                            "R3", node,
                            f"{name}(dtype=int32) can overflow at 64-bit "
                            "scale (edge counts / prefix sums / cut "
                            "accumulators); use dtypes.ACC_DTYPE",
                        )
            if (
                name == "astype"
                and node.args
                and _is_int32(node.args[0], ctx)
                and isinstance(node.func, ast.Attribute)
            ):
                for sub in ast.walk(node.func.value):
                    if (
                        isinstance(sub, ast.Call)
                        and _terminal_name(sub.func) in ACC_CALLS
                    ):
                        self._emit(
                            "R3", node,
                            "narrowing a reduction result to int32 "
                            "discards the 64-bit accumulator policy; "
                            "use dtypes.ACC_DTYPE",
                        )
                        break

        # R4: jit wrapper constructed per iteration / per evaluation
        if _is_jit_decorator(node, ctx):
            if self.loop_depth:
                self._emit(
                    "R4", node,
                    "jit wrapper constructed inside a loop compiles per "
                    "iteration; hoist it (jit caches by function identity)",
                )
            elif (
                node.args
                and isinstance(node.args[0], ast.Lambda)
                and self.func_stack
            ):
                self._emit(
                    "R4", node,
                    "jax.jit of a fresh lambda retraces on every call of "
                    "the enclosing function; define the jitted function "
                    "at module level",
                )

        # R6: eager device-memory/cost introspection outside the gated
        # perf-barrier helpers
        if not ctx.is_perf_gate_module:
            if q in R6_QUERIES:
                self._emit(
                    "R6", node,
                    f"direct {q}() walks device state eagerly (R2's "
                    "hazard class); route through the gated perf "
                    "helpers (telemetry.perf.sample_memory / "
                    "utils.heap_profiler)",
                )
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in R6_METHODS
            ):
                self._emit(
                    "R6", node,
                    f".{node.func.attr}() introspects a compiled "
                    "executable/device eagerly; the perf observatory "
                    "(telemetry/perf.py) captures this at the compile "
                    "boundary — use its snapshot instead",
                )

        # call-graph pass (one-level inlining): a factored helper is no
        # longer assumed clean — the hazard fires AT THE CALL SITE,
        # where the staging fix belongs
        resolved = ctx.resolve_call(
            node, self.class_stack[-1] if self.class_stack else None
        )
        if resolved is not None and resolved.node not in self.func_stack:
            summary = ctx.helper_summary(resolved)
            # R1d: a call inside a span scope to a helper whose body
            # host-syncs distorts the span exactly like the inline pull
            # (the "factored into a helper" idiom, now verified).  Only
            # SAME-MODULE helpers are inlined here: a cross-module call
            # from a phase span lands on one of the package's
            # host-boundary APIs (host_graph_from_device, the host
            # refiners, quality notes), whose hostness is the hybrid
            # architecture's contract, not a hidden refactor artifact —
            # the documented blind spot (docs/static_analysis.md).
            if (
                self.span_depth > 0
                and summary.host_syncs
                and resolved.module is ctx.module_info
            ):
                hline, hdesc = summary.host_syncs[0]
                self._emit(
                    "R1", node,
                    f"call to '{resolved.qualname}' inside a telemetry "
                    f"span scope reaches a host sync ({hdesc} at "
                    f"{resolved.module.path}:{hline}); stage the pull "
                    "outside the span",
                )
            if not self.func_stack:
                # R2b/R6b: import-time reach — the helper may live in a
                # gate module (platform/perf), where the def site is
                # exempt, but CALLING it at import time still eagerly
                # initializes the backend (the test_capi hang class)
                if summary.device_queries:
                    qline, qdesc = summary.device_queries[0]
                    self._emit(
                        "R2", node,
                        f"import-time call to '{resolved.qualname}' "
                        f"reaches {qdesc} ({resolved.module.path}:"
                        f"{qline}); defer it into a function",
                    )
                if summary.perf_introspections:
                    pline, pdesc = summary.perf_introspections[0]
                    self._emit(
                        "R6", node,
                        f"import-time call to '{resolved.qualname}' "
                        f"reaches {pdesc} ({resolved.module.path}:"
                        f"{pline}); defer it behind the perf gate",
                    )

        self.generic_visit(node)


def run_rules(ctx: ModuleContext) -> List[Finding]:
    walker = _RuleWalker(ctx)
    walker.visit(ctx.tree)
    return walker.findings
