"""The jax-aware passes: host-sync-in-traced-code (GL1xx), recompile
hazards (GL2xx), donation safety (GL3xx). All three share the
``callgraph.CallGraph`` jit-root reachability.

Code catalog (docs/STATIC_ANALYSIS.md):

- GL101 ``.item()`` inside jit-reachable code
- GL102 ``float()/int()/bool()`` on an array-valued expression in traced code
- GL103 ``np.asarray``/``np.array`` in traced code (host transfer / trace break)
- GL104 ``jax.device_get`` in traced code
- GL105 ``print`` in traced code (host callback per trace, silent sync)
- GL106 tracker/metrics publish call in traced code
- GL201 jitted closure captures shape-derived Python values (per-shape
  silent recompile; intentional shape-bucket caches get baselined)
- GL202 ``jax.jit``/``pjit`` called inside a loop (fresh executable per
  iteration: the jit cache keys on function object identity)
- GL203 jitted function uses a parameter as a Python shape/loop bound
  without ``static_argnums``
- GL204 ``jax.jit(lambda ...)`` in function scope (a fresh lambda object
  per call defeats the jit cache)
- GL301 read of a variable after it was passed in a donated position
  (donated buffers may alias the outputs — reads see garbage)
"""

import ast
from typing import Dict, List, Sequence, Set, Tuple

from trlx_tpu.analysis.callgraph import (
    CallGraph,
    FunctionInfo,
    attr_chain,
)
from trlx_tpu.analysis.core import (
    AnalysisContext,
    Finding,
    LintPass,
    register_pass,
)

# array-producing method names: a float()/int()/bool() around one of these
# is a device scalar forced to host
_ARRAY_METHODS = {
    "sum", "mean", "max", "min", "prod", "any", "all", "dot", "norm",
    "astype", "squeeze", "reshape",
}
_HOST_CONVERTERS = {"float", "int", "bool"}


def _unparse(node: ast.AST, limit: int = 60) -> str:
    try:
        text = ast.unparse(node)
    except Exception:  # pragma: no cover - very old nodes
        text = "<expr>"
    return text if len(text) <= limit else text[: limit - 1] + "…"


def _builtin_unshadowed(
    graph: CallGraph, name: str, fn: FunctionInfo
) -> bool:
    scope = fn
    while scope is not None:
        if name in scope.bound:
            return False
        scope = scope.parent
    return name not in graph.imports.get(fn.module.modname, {})


def _contains_shape_access(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in ("shape", "ndim", "size"):
            return True
    return False


def _looks_array_valued(graph: CallGraph, node: ast.AST, fn: FunctionInfo) -> bool:
    """Heuristic: the expression produces a device array (a jnp/jax call or
    an array-method call somewhere inside). Shape arithmetic is excluded —
    ``int(x.shape[1])`` is static, not a sync."""
    if _contains_shape_access(node):
        return False
    for sub in ast.walk(node):
        if not isinstance(sub, ast.Call):
            continue
        name = graph.external_name(sub.func, fn, fn.module)
        if name and (name.startswith("jax.") or name.startswith("jnp.")):
            return True
        if isinstance(sub.func, ast.Attribute) and sub.func.attr in _ARRAY_METHODS:
            return True
    return False


@register_pass
class HostSyncPass(LintPass):
    name = "host-sync"
    codes = ("GL101", "GL102", "GL103", "GL104", "GL105", "GL106")
    description = "host round-trips inside jit-reachable code"

    def run(self, ctx: AnalysisContext) -> List[Finding]:
        graph = ctx.callgraph
        findings: List[Finding] = []
        for fn in graph.traced_functions():
            via = graph.traced_via.get(fn.full, "?")
            for node in fn.body_nodes():
                if not isinstance(node, ast.Call):
                    continue
                findings.extend(self._check_call(graph, fn, node, via))
        return findings

    def _check_call(
        self, graph: CallGraph, fn: FunctionInfo, node: ast.Call, via: str
    ) -> List[Finding]:
        out: List[Finding] = []

        def emit(code: str, detail: str, message: str) -> None:
            out.append(
                Finding(
                    code=code,
                    path=fn.module.relpath,
                    line=node.lineno,
                    symbol=fn.qualname,
                    detail=detail,
                    message=f"{message} inside jit-reachable code "
                    f"(traced via root `{via}`)",
                )
            )

        func = node.func
        if isinstance(func, ast.Attribute) and func.attr == "item" and not node.args:
            emit("GL101", ".item", f"`{_unparse(func)}()` forces a device→host sync")
            return out
        if isinstance(func, ast.Name) and func.id in _HOST_CONVERTERS:
            if (
                node.args
                and _builtin_unshadowed(graph, func.id, fn)
                and _looks_array_valued(graph, node.args[0], fn)
            ):
                emit(
                    "GL102",
                    f"{func.id}()",
                    f"`{func.id}()` on an array value concretizes the tracer "
                    "(host sync / ConcretizationError)",
                )
            return out
        name = graph.external_name(func, fn, fn.module)
        if name in ("numpy.asarray", "numpy.array"):
            emit(
                "GL103",
                name.split(".", 1)[1],
                f"`{_unparse(func)}` pulls the traced value to host "
                "(use jnp, or hoist to the host stage)",
            )
        elif name == "jax.device_get":
            emit("GL104", "device_get", "`jax.device_get` is a blocking host fetch")
        elif isinstance(func, ast.Name) and func.id == "print":
            if _builtin_unshadowed(graph, "print", fn):
                emit(
                    "GL105",
                    "print",
                    "`print` in traced code runs at trace time only (or "
                    "syncs via callback) — use jax.debug.print or hoist",
                )
        else:
            chain = attr_chain(func)
            if chain and any("tracker" in part for part in chain[:-1]):
                emit(
                    "GL106",
                    ".".join(chain),
                    f"tracker call `{_unparse(func)}` publishes from traced "
                    "code — trackers are host-side, log from the learn loop",
                )
        return out


# ---------------------------------------------------------------------------
# recompile hazards
# ---------------------------------------------------------------------------


def _rhs_is_shape_derived(node: ast.AST) -> bool:
    """RHS mentions ``.shape``/``len()`` or a name carrying "shape" — the
    classic per-shape constant that forks compilations when it changes."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in ("shape", "ndim"):
            return True
        if (
            isinstance(sub, ast.Call)
            and isinstance(sub.func, ast.Name)
            and sub.func.id == "len"
        ):
            return True
        if isinstance(sub, ast.Name) and "shape" in sub.id.lower():
            return True
    return False


@register_pass
class RecompileHazardPass(LintPass):
    name = "recompile-hazard"
    codes = ("GL201", "GL202", "GL203", "GL204")
    description = "patterns that silently fork XLA compilations"

    def run(self, ctx: AnalysisContext) -> List[Finding]:
        graph = ctx.callgraph
        findings: List[Finding] = []
        findings.extend(self._jit_in_loop_and_lambda(graph))
        for root in graph.jit_roots:
            findings.extend(self._closure_hazards(graph, root))
            findings.extend(self._static_argnum_hazards(graph, root))
        # one finding per key (a fn jitted at 2 sites reports once)
        seen: Set[str] = set()
        unique = []
        for f in findings:
            if f.key not in seen:
                seen.add(f.key)
                unique.append(f)
        return unique

    def _jit_in_loop_and_lambda(self, graph: CallGraph) -> List[Finding]:
        out: List[Finding] = []
        for mod in graph.ctx.modules:
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                scope = graph.enclosing_function(mod, node)
                name = graph.external_name(node.func, scope, mod)
                if not graph.is_jit_name(name):
                    continue
                symbol = scope.qualname if scope else "-"
                in_loop = any(
                    isinstance(anc, (ast.For, ast.While))
                    for anc in mod.ancestors(node)
                )
                if in_loop:
                    out.append(
                        Finding(
                            code="GL202",
                            path=mod.relpath,
                            line=node.lineno,
                            symbol=symbol,
                            detail=name.rsplit(".", 1)[-1],
                            message=f"`{name}` called inside a loop: the jit "
                            "cache keys on function identity, so every "
                            "iteration may compile a fresh executable — "
                            "hoist the jit out of the loop",
                        )
                    )
                if (
                    node.args
                    and isinstance(node.args[0], ast.Lambda)
                    and scope is not None
                ):
                    out.append(
                        Finding(
                            code="GL204",
                            path=mod.relpath,
                            line=node.lineno,
                            symbol=symbol,
                            detail="lambda",
                            message=f"`{name}(lambda ...)` in function scope: "
                            "a fresh lambda object per call defeats the jit "
                            "cache (recompile every invocation) — name the "
                            "function once",
                        )
                    )
        return out

    def _closure_hazards(self, graph: CallGraph, root) -> List[Finding]:
        fn = root.fn
        if fn.parent is None:
            return []  # module-level function: captures are module constants
        free_shape_derived: List[str] = []
        loads = {
            sub.id
            for sub in ast.walk(fn.node)
            if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load)
        }
        for name in sorted(loads - fn.bound):
            # find the binding scope and how the name is assigned there
            scope = fn.parent
            while scope is not None and name not in scope.bound:
                scope = scope.parent
            if scope is None or name in scope.nested:
                continue
            if name in scope.params:
                if "shape" in name.lower():
                    free_shape_derived.append(name)
                continue
            for node in scope.body_nodes():
                if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == name
                    or isinstance(t, ast.Tuple)
                    and any(
                        isinstance(e, ast.Name) and e.id == name for e in t.elts
                    )
                    for t in node.targets
                ):
                    if _rhs_is_shape_derived(node.value):
                        free_shape_derived.append(name)
                        break
        if not free_shape_derived:
            return []
        names = ",".join(sorted(set(free_shape_derived)))
        return [
            Finding(
                code="GL201",
                path=fn.module.relpath,
                line=getattr(fn.node, "lineno", root.line),
                symbol=fn.qualname,
                detail=names,
                message=f"jitted closure captures shape-derived Python "
                f"value(s) `{names}`: every new shape silently compiles a "
                "new program — key a program cache on them (and baseline "
                "it) or pass them as static_argnums",
            )
        ]

    def _static_argnum_hazards(self, graph: CallGraph, root) -> List[Finding]:
        fn = root.fn
        if root.static_argnums:
            return []
        hazards: List[str] = []
        params = set(fn.params[1:] if fn.class_full else fn.params)
        for node in fn.body_nodes():
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "range"
                and node.args
                and isinstance(node.args[0], ast.Name)
                and node.args[0].id in params
            ):
                hazards.append(node.args[0].id)
        if not hazards:
            return []
        names = ",".join(sorted(set(hazards)))
        return [
            Finding(
                code="GL203",
                path=fn.module.relpath,
                line=getattr(fn.node, "lineno", root.line),
                symbol=fn.qualname,
                detail=names,
                message=f"jitted function drives `range()` with parameter(s) "
                f"`{names}` but the jit call has no static_argnums: the "
                "value is traced, so Python iteration fails or retraces — "
                "mark it static",
            )
        ]


# ---------------------------------------------------------------------------
# donation safety
# ---------------------------------------------------------------------------


def _flatten_targets(stmt: ast.stmt) -> List[Tuple[str, ...]]:
    """Assignment-target chains of a statement: ``self.state, x = ...`` →
    [("self","state"), ("x",)]."""
    targets: List[ast.AST] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)) and stmt.target is not None:
        targets = [stmt.target]
    out: List[Tuple[str, ...]] = []
    work = list(targets)
    while work:
        t = work.pop()
        if isinstance(t, (ast.Tuple, ast.List)):
            work.extend(t.elts)
            continue
        chain = attr_chain(t)
        if chain:
            out.append(tuple(chain))
    return out


def _linear_statements(fn: FunctionInfo) -> List[ast.stmt]:
    """The function's statements in source order, control-flow bodies
    flattened (if/else/loop/with/try bodies inline; nested defs excluded)."""
    out: List[ast.stmt] = []

    def walk(body: Sequence[ast.stmt]) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                continue
            out.append(stmt)
            for name in ("body", "orelse", "finalbody"):
                sub = getattr(stmt, name, None)
                if isinstance(sub, list):
                    walk(sub)
            for handler in getattr(stmt, "handlers", []) or []:
                walk(handler.body)

    walk(fn.body_statements())
    return out


def _stmt_load_chains(stmt: ast.stmt) -> List[Tuple[Tuple[str, ...], int]]:
    """(chain, lineno) of every Name/attribute *load* in the statement,
    excluding nested function bodies."""
    out: List[Tuple[Tuple[str, ...], int]] = []
    skip_bodies: List[ast.AST] = []
    work: List[ast.AST] = [stmt]
    while work:
        node = work.pop()
        if node is not stmt and isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        ):
            continue
        # only the *sub-statements'* own expressions matter; bodies are
        # visited as their own statements by _linear_statements
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.stmt):
                continue
            work.append(child)
        if isinstance(node, ast.Attribute) and isinstance(node.ctx, ast.Load):
            chain = attr_chain(node)
            if chain:
                out.append((tuple(chain), node.lineno))
        elif isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            out.append(((node.id,), node.lineno))
    return out


@register_pass
class DonationSafetyPass(LintPass):
    name = "donation-safety"
    codes = ("GL301",)
    description = "reads of buffers already donated to a jitted call"

    def run(self, ctx: AnalysisContext) -> List[Finding]:
        graph = ctx.callgraph
        self._factories = self._donating_factories(graph)
        self._attrs = self._donating_attrs(graph)
        self._module_vars: Dict[Tuple[str, str], Tuple[int, ...]] = {}
        for mod in ctx.modules:
            for stmt in mod.tree.body:
                if not isinstance(stmt, ast.Assign):
                    continue
                donate = self._jit_donate(graph, stmt.value, None, mod)
                if not donate:
                    continue
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        self._module_vars[(mod.modname, t.id)] = donate
        findings: List[Finding] = []
        for fn in graph.functions:
            if isinstance(fn.node, ast.Lambda):
                continue
            findings.extend(self._check_function(graph, fn))
        return findings

    # -- which callables donate -----------------------------------------

    def _jit_donate(self, graph: CallGraph, node: ast.AST, scope, mod) -> Tuple[int, ...]:
        """donate_argnums of a ``jax.jit(...)`` expression, or of a stored
        program's constructor (else ())."""
        if not isinstance(node, ast.Call):
            return ()
        if graph.jit_call(node, scope, mod) is None:
            return ()
        _, donate = graph._jit_kwargs(node)
        return donate

    def _local_donators(
        self, graph: CallGraph, fn: FunctionInfo
    ) -> Dict[str, Tuple[int, ...]]:
        out: Dict[str, Tuple[int, ...]] = {}
        for stmt in _linear_statements(fn):
            if not isinstance(stmt, ast.Assign):
                continue
            donate = self._jit_donate(graph, stmt.value, fn, fn.module)
            if not donate:
                continue
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    out[t.id] = donate
        return out

    def _donating_factories(self, graph: CallGraph) -> Dict[str, Tuple[int, ...]]:
        """FunctionInfo.full → argnums, for functions whose return value is
        a donating jitted callable."""
        out: Dict[str, Tuple[int, ...]] = {}
        for fn in graph.functions:
            if isinstance(fn.node, ast.Lambda):
                continue
            local = self._local_donators(graph, fn)
            for stmt in _linear_statements(fn):
                if not isinstance(stmt, ast.Return) or stmt.value is None:
                    continue
                donate = self._jit_donate(graph, stmt.value, fn, fn.module)
                if not donate and isinstance(stmt.value, ast.Name):
                    donate = local.get(stmt.value.id, ())
                if donate:
                    out[fn.full] = donate
        return out

    def _donating_attrs(self, graph: CallGraph) -> Dict[Tuple[str, str], Tuple[int, ...]]:
        """(class_full, attr) → argnums for ``self.attr = <donating>``."""
        out: Dict[Tuple[str, str], Tuple[int, ...]] = {}
        for fn in graph.functions:
            cls = fn.class_full or graph._enclosing_class(fn)
            if cls is None or isinstance(fn.node, ast.Lambda):
                continue
            for stmt in _linear_statements(fn):
                if not isinstance(stmt, ast.Assign):
                    continue
                donate = self._jit_donate(graph, stmt.value, fn, fn.module)
                if not donate and isinstance(stmt.value, ast.Call):
                    for callee in graph.resolve_callable(
                        stmt.value.func, fn, fn.module
                    ):
                        if callee.full in self._factories:
                            donate = self._factories[callee.full]
                            break
                if not donate:
                    continue
                for t in stmt.targets:
                    chain = attr_chain(t)
                    if chain and len(chain) == 2 and chain[0] == "self":
                        out[(cls, chain[1])] = donate
        return out

    def _call_donate_argnums(
        self, graph: CallGraph, fn: FunctionInfo, call: ast.Call,
        local: Dict[str, Tuple[int, ...]],
    ) -> Tuple[int, ...]:
        func = call.func
        # jax.jit(f, donate_argnums=...)(args) immediately invoked
        donate = self._jit_donate(graph, func, fn, fn.module)
        if donate:
            return donate
        if isinstance(func, ast.Name):
            hit = local.get(func.id, ())
            if hit:
                return hit
            scope = fn
            while scope is not None:
                if func.id in scope.bound and func.id not in local:
                    return ()  # shadowed by a non-donating local
                scope = scope.parent
            return self._module_vars.get((fn.module.modname, func.id), ())
        chain = attr_chain(func)
        if chain and len(chain) == 2 and chain[0] == "self":
            cls = fn.class_full or graph._enclosing_class(fn)
            if cls:
                for related in graph.related_classes(cls):
                    hit = self._attrs.get((related, chain[1]))
                    if hit:
                        return hit
        return ()

    # -- read-after-donate scan ------------------------------------------

    def _check_function(self, graph: CallGraph, fn: FunctionInfo) -> List[Finding]:
        local = self._local_donators(graph, fn)
        statements = _linear_statements(fn)
        donated: Dict[Tuple[str, ...], int] = {}  # chain -> donation line
        findings: List[Finding] = []
        reported: Set[Tuple[str, ...]] = set()
        for stmt in statements:
            rebinds = _flatten_targets(stmt)
            # 1) reads of already-donated chains (this statement's loads)
            if donated:
                for chain, line in _stmt_load_chains(stmt):
                    for d_chain, d_line in list(donated.items()):
                        if (
                            chain[: len(d_chain)] == d_chain
                            and line > d_line
                            and d_chain not in reported
                        ):
                            reported.add(d_chain)
                            findings.append(
                                Finding(
                                    code="GL301",
                                    path=fn.module.relpath,
                                    line=line,
                                    symbol=fn.qualname,
                                    detail=".".join(d_chain),
                                    message=f"`{'.'.join(chain)}` is read after "
                                    f"`{'.'.join(d_chain)}` was donated to a "
                                    f"jitted call on line {d_line} — donated "
                                    "buffers may alias the outputs (garbage "
                                    "reads / heap corruption)",
                                )
                            )
            # 2) rebinding clears tracking
            for chain in rebinds:
                for d_chain in list(donated):
                    if d_chain[: len(chain)] == tuple(chain):
                        del donated[d_chain]
            # 3) new donations from calls in this statement (skipping nested
            # function subtrees — their bodies are separate scopes, but the
            # rest of the statement must still be scanned)
            work: List[ast.AST] = [stmt]
            calls: List[ast.Call] = []
            while work:
                node = work.pop()
                if node is not stmt and isinstance(
                    node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
                ):
                    continue
                work.extend(ast.iter_child_nodes(node))
                if isinstance(node, ast.Call):
                    calls.append(node)
            for node in calls:
                argnums = self._call_donate_argnums(graph, fn, node, local)
                for pos in argnums:
                    if pos < 0 or pos >= len(node.args):
                        continue
                    chain = attr_chain(node.args[pos])
                    if not chain:
                        continue
                    chain_t = tuple(chain)
                    if chain_t in [tuple(r) for r in rebinds]:
                        continue  # rebound by this very statement
                    donated.setdefault(chain_t, node.lineno)
        return findings
