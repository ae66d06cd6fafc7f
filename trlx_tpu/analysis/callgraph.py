"""Intra-package call graph with jit-root reachability.

Static (AST-only) approximation of "which functions execute under a JAX
trace": every ``jax.jit`` / ``pjit`` / ``shard_map`` call or decorator whose
target resolves to a function defined in the package becomes a **root**,
and reachability over resolved intra-package edges marks the **traced**
set the jax-aware passes (``jax_passes.py``) inspect.

Resolution is deliberately heuristic — sound enough for a linter, never for
a compiler:

- lexical scoping: a called name resolves to a nested ``def`` in an
  enclosing function, then a module-level function, then an import;
- imports follow re-export chains (``trlx_tpu.parallel.make_mesh`` →
  ``trlx_tpu.parallel.mesh.make_mesh``) with a cycle guard;
- ``self.m()`` resolves to ``m`` on the enclosing class, its package
  superclasses, AND all package subclasses (over-approximation: the
  abstract ``loss_fn`` pulls every trainer's implementation into the
  traced set — exactly what the host-sync gate wants);
- annotated locals/params (``method: PPOConfig = ...``) resolve one more
  attribute hop (``method.loss`` → ``PPOConfig.loss``);
- a bare *reference* to a package function inside a traced body counts as
  an edge (functions passed to ``lax.while_loop``/``scan``/``vmap`` are
  traced even though never "called" syntactically).

Higher-order flow through parameters (``adjust_logits=...``) is not
tracked; the traced set is an under-approximation there and an
over-approximation for shared helpers — both documented in
docs/STATIC_ANALYSIS.md.
"""

import ast
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, Iterator, List, Optional, Set, Tuple

from trlx_tpu.analysis.core import AnalysisContext, SourceModule

__all__ = [
    "CallGraph",
    "ExceptionFlow",
    "FunctionInfo",
    "ClassInfo",
    "JitRoot",
    "ThreadRoot",
    "attr_chain",
]

# canonical dotted names that open a trace when called with a function
JIT_WRAPPERS = {
    "jax.jit",
    "jax.pjit",
    "pjit.pjit",
    "jax.experimental.pjit.pjit",
    "jax.shard_map",
    "jax.experimental.shard_map.shard_map",
}
PARTIAL_NAMES = {"functools.partial", "partial"}
# the program store's constructors (``trlx_tpu/utils/programs.py``): a
# ``jax.jit`` kept from one start to the next, called as
# ``stored_program(name, fn, key_parts, ...)`` or, through a job's store,
# ``<...>.programs.program(name, fn, *parts, ...)``: the function is the
# SECOND argument, the jit keywords ride the call as they do ``jax.jit``'s
STORED_JIT = "trlx_tpu.utils.programs.stored_program"

# canonical dotted names whose `target=` keyword starts a new thread of
# control (the thread-root constructors the escape analysis keys on)
THREAD_CONSTRUCTORS = {
    "threading.Thread",
    "multiprocessing.Process",
}

# stdlib HTTP handler base classes: a ``ThreadingHTTPServer`` runs every
# ``do_*`` method of its handler class on a per-connection thread, so
# handler methods are thread roots with no visible Thread(...) spawn —
# the serve frontend's "handlers only touch the submit surface" contract
# is exactly what the escape analysis must see them as (docs/SERVING.md)
HTTP_HANDLER_BASES = {
    "http.server.BaseHTTPRequestHandler",
    "http.server.SimpleHTTPRequestHandler",
    "http.server.CGIHTTPRequestHandler",
    "socketserver.BaseRequestHandler",
    "socketserver.StreamRequestHandler",
}


def attr_chain(node: ast.AST) -> Optional[List[str]]:
    """``a.b.c`` → ["a","b","c"]; None if any link isn't a plain Name/attr."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return None


@dataclass
class FunctionInfo:
    qualname: str  # module-relative, e.g. "Cls.m.<locals>.step_fn"
    full: str  # modname + "." + qualname
    module: SourceModule
    node: ast.AST  # FunctionDef | AsyncFunctionDef | Lambda
    class_full: Optional[str] = None  # innermost enclosing class
    parent: Optional["FunctionInfo"] = None
    # name → every nested def with that name (branches re-define `fn`)
    nested: Dict[str, List["FunctionInfo"]] = field(default_factory=dict)
    params: List[str] = field(default_factory=list)
    bound: Set[str] = field(default_factory=set)  # names assigned in scope
    var_types: Dict[str, str] = field(default_factory=dict)  # name -> class full

    def body_nodes(self) -> Iterator[ast.AST]:
        """Walk this function's own body, not descending into nested
        functions/lambdas/classes (their bodies belong to their own infos)."""
        stack: List[ast.AST] = list(ast.iter_child_nodes(self.node))
        while stack:
            node = stack.pop()
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.ClassDef)
            ):
                continue
            yield node
            stack.extend(ast.iter_child_nodes(node))

    def body_statements(self) -> List[ast.stmt]:
        body = getattr(self.node, "body", None)
        return body if isinstance(body, list) else []


@dataclass
class ClassInfo:
    name: str
    full: str
    module: SourceModule
    node: ast.ClassDef
    base_names: List[str] = field(default_factory=list)  # resolved dotted
    methods: Dict[str, FunctionInfo] = field(default_factory=dict)
    # names assigned at class scope (fields, `from_dict = classmethod(...)`)
    class_attrs: Set[str] = field(default_factory=set)


@dataclass
class JitRoot:
    fn: FunctionInfo
    wrapper: str  # the jit-family name used
    module: SourceModule
    line: int
    static_argnums: Tuple[int, ...] = ()
    donate_argnums: Tuple[int, ...] = ()


@dataclass
class ThreadRoot:
    """One function that starts executing on its own thread of control:
    the ``target=`` of a ``threading.Thread``/``multiprocessing.Process``
    constructor, or the callable handed to an ``.submit(...)`` call
    (``concurrent.futures`` executors AND the package's own
    ``RolloutPipeline.submit`` — both run the callable on a worker
    thread). Resolution reuses the jit-root machinery: closures, bound
    ``self.m`` methods, ``partial(f, x)`` wrapping, factory returns, and
    lambdas all resolve (``resolve_callable_deep``). ``do_*`` methods of
    ``BaseHTTPRequestHandler`` subclasses are roots too (via
    "http-handler"): a ``ThreadingHTTPServer`` dispatches each request
    on a per-connection thread the stdlib spawns internally."""

    fn: FunctionInfo
    via: str  # "Thread" | "Process" | "submit" | "http-handler"
    module: SourceModule
    line: int


def _int_tuple(node: Optional[ast.AST]) -> Tuple[int, ...]:
    """Literal int / tuple-of-ints keyword value (else empty)."""
    if node is None:
        return ()
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, int):
                out.append(elt.value)
        return tuple(out)
    return ()


class _ModuleIndexer(ast.NodeVisitor):
    """One pass over a module: imports, functions (incl. nested + lambdas),
    classes and their methods."""

    def __init__(self, graph: "CallGraph", module: SourceModule):
        self.graph = graph
        self.module = module
        self.scope: List[FunctionInfo] = []
        self.classes: List[ClassInfo] = []

    # -- imports --------------------------------------------------------

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            name = alias.asname or alias.name.split(".")[0]
            target = alias.name if alias.asname else alias.name.split(".")[0]
            self.graph.imports[self.module.modname][name] = target
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        base = node.module or ""
        if node.level:
            parts = self.module.modname.split(".")
            is_package = self.module.relpath.endswith("__init__.py")
            # level 1 from a package = the package itself; from a module =
            # its parent package; each further level pops one more
            drop = node.level - 1 if is_package else node.level
            parts = parts[: len(parts) - drop] if drop else parts
            base = ".".join(parts + ([node.module] if node.module else []))
        for alias in node.names:
            if alias.name == "*":
                continue
            name = alias.asname or alias.name
            self.graph.imports[self.module.modname][name] = f"{base}.{alias.name}"
        self.generic_visit(node)

    # -- scopes ---------------------------------------------------------

    def _qualname(self, name: str) -> str:
        if self.scope:
            return f"{self.scope[-1].qualname}.<locals>.{name}"
        if self.classes:
            return f"{self.classes[-1].name}.{name}"
        return name

    def _make_function(self, node, name: str) -> FunctionInfo:
        qual = self._qualname(name)
        info = FunctionInfo(
            qualname=qual,
            full=f"{self.module.modname}.{qual}",
            module=self.module,
            node=node,
            class_full=(
                self.classes[-1].full if self.classes and not self.scope else None
            ),
            parent=self.scope[-1] if self.scope else None,
        )
        args = node.args
        for a in (
            list(args.posonlyargs)
            + list(args.args)
            + list(args.kwonlyargs)
            + ([args.vararg] if args.vararg else [])
            + ([args.kwarg] if args.kwarg else [])
        ):
            info.params.append(a.arg)
            info.bound.add(a.arg)
            ann = getattr(a, "annotation", None)
            cls_full = self.graph._annotation_class(ann, self.module)
            if cls_full:
                info.var_types[a.arg] = cls_full
        return info

    def _enter_function(self, node, name: str) -> None:
        info = self._make_function(node, name)
        if info.full in self.graph.function_index:
            # same-named defs in sibling branches (`def fn` per sampler
            # flavor): `full` must be unique for reachability bookkeeping;
            # `qualname` (the baseline symbol) intentionally stays shared
            k = 2
            while f"{info.full}#{k}" in self.graph.function_index:
                k += 1
            info.full = f"{info.full}#{k}"
        self.graph.functions.append(info)
        self.graph.function_index[info.full] = info
        if info.parent is not None:
            info.parent.nested.setdefault(name, []).append(info)
            info.parent.bound.add(name)
        elif self.classes:
            self.classes[-1].methods[name] = info
            self.classes[-1].class_attrs.add(name)
        else:
            self.graph.module_functions[self.module.modname][name] = info
        self.scope.append(info)
        # bind/type locals of the new scope
        for child in ast.walk(node):
            if isinstance(child, ast.Assign):
                for tgt in child.targets:
                    for sub in ast.walk(tgt):
                        if isinstance(sub, ast.Name):
                            info.bound.add(sub.id)
            elif isinstance(child, ast.AnnAssign) and isinstance(
                child.target, ast.Name
            ):
                info.bound.add(child.target.id)
                cls_full = self.graph._annotation_class(
                    child.annotation, self.module
                )
                if cls_full:
                    info.var_types[child.target.id] = cls_full
            elif isinstance(child, (ast.For, ast.AsyncFor)):
                for sub in ast.walk(child.target):
                    if isinstance(sub, ast.Name):
                        info.bound.add(sub.id)
            elif isinstance(child, ast.withitem) and child.optional_vars:
                for sub in ast.walk(child.optional_vars):
                    if isinstance(sub, ast.Name):
                        info.bound.add(sub.id)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._enter_function(node, node.name)
        self.generic_visit(node)
        self.scope.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node: ast.Lambda) -> None:
        self._enter_function(node, f"<lambda:L{node.lineno}>")
        self.generic_visit(node)
        self.scope.pop()

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        if self.scope:  # classes inside functions: skip (rare, test-only)
            self.generic_visit(node)
            return
        qual = f"{self.classes[-1].name}.{node.name}" if self.classes else node.name
        info = ClassInfo(
            name=node.name,
            full=f"{self.module.modname}.{qual}",
            module=self.module,
            node=node,
        )
        for base in node.bases:
            chain = attr_chain(base)
            if chain:
                info.base_names.append(".".join(chain))
        for stmt in node.body:
            if isinstance(stmt, ast.Assign):
                for tgt in stmt.targets:
                    if isinstance(tgt, ast.Name):
                        info.class_attrs.add(tgt.id)
            elif isinstance(stmt, ast.AnnAssign) and isinstance(
                stmt.target, ast.Name
            ):
                info.class_attrs.add(stmt.target.id)
        self.graph.classes[info.full] = info
        self.graph.classes_by_name.setdefault(info.name, []).append(info)
        self.classes.append(info)
        self.generic_visit(node)
        self.classes.pop()


class CallGraph:
    def __init__(self, ctx: AnalysisContext):
        self.ctx = ctx
        self.functions: List[FunctionInfo] = []
        self.function_index: Dict[str, FunctionInfo] = {}
        self.module_functions: Dict[str, Dict[str, FunctionInfo]] = {}
        self.classes: Dict[str, ClassInfo] = {}
        self.classes_by_name: Dict[str, List[ClassInfo]] = {}
        self.imports: Dict[str, Dict[str, str]] = {}
        self.modules_by_name: Dict[str, SourceModule] = {}
        self.jit_roots: List[JitRoot] = []
        self.traced: Set[str] = set()  # FunctionInfo.full
        self.traced_via: Dict[str, str] = {}  # full -> root qualname
        self.thread_roots: List[ThreadRoot] = []
        self._thread_membership: Optional[Dict[str, FrozenSet[str]]] = None
        self._build()

    # -- construction ----------------------------------------------------

    def _build(self) -> None:
        for mod in self.ctx.modules:
            self.imports[mod.modname] = {}
            self.module_functions[mod.modname] = {}
            self.modules_by_name[mod.modname] = mod
        for mod in self.ctx.modules:
            _ModuleIndexer(self, mod).visit(mod.tree)
        self._link_classes()
        self._collect_jit_roots()
        self._mark_traced()
        self._collect_thread_roots()

    def _link_classes(self) -> None:
        self._supers: Dict[str, Set[str]] = {}
        self._subs: Dict[str, Set[str]] = {}
        for full, info in self.classes.items():
            for base in info.base_names:
                resolved = self._resolve_dotted_class(base, info.module)
                if resolved:
                    self._supers.setdefault(full, set()).add(resolved.full)
                    self._subs.setdefault(resolved.full, set()).add(full)

    def _closure(self, start: str, edges: Dict[str, Set[str]]) -> Set[str]:
        seen = {start}
        work = [start]
        while work:
            for nxt in edges.get(work.pop(), ()):
                if nxt not in seen:
                    seen.add(nxt)
                    work.append(nxt)
        return seen

    def related_classes(self, full: str) -> Set[str]:
        """The class plus its package super- and subclass closure — the
        candidate set for ``self.m()`` resolution."""
        return self._closure(full, self._supers) | self._closure(full, self._subs)

    # -- name resolution -------------------------------------------------

    def _resolve_import_target(
        self, target: str, _seen: Optional[Set[str]] = None
    ) -> Optional[FunctionInfo]:
        """A dotted import target → package function, following re-exports."""
        if target in self.function_index:
            return self.function_index[target]
        _seen = _seen or set()
        if target in _seen or "." not in target:
            return None
        _seen.add(target)
        modpath, name = target.rsplit(".", 1)
        fn = self.module_functions.get(modpath, {}).get(name)
        if fn is not None:
            return fn
        re_export = self.imports.get(modpath, {}).get(name)
        if re_export:
            return self._resolve_import_target(re_export, _seen)
        return None

    def _resolve_dotted_class(
        self, dotted: str, module: SourceModule, _seen: Optional[Set[str]] = None
    ) -> Optional[ClassInfo]:
        _seen = _seen or set()
        if dotted in _seen:
            return None
        _seen.add(dotted)
        if dotted in self.classes:
            return self.classes[dotted]
        head, _, rest = dotted.partition(".")
        target = self.imports.get(module.modname, {}).get(head)
        if target:
            full = f"{target}.{rest}" if rest else target
            if full in self.classes:
                return self.classes[full]
            if "." in full:
                modpath, name = full.rsplit(".", 1)
                re_export = self.imports.get(modpath, {}).get(name)
                if re_export:
                    mod = self.modules_by_name.get(modpath)
                    if mod is not None:
                        return self._resolve_dotted_class(re_export, mod, _seen)
                    if re_export in self.classes:
                        return self.classes[re_export]
        # same-module class
        local = f"{module.modname}.{dotted}"
        return self.classes.get(local)

    def _annotation_class(
        self, ann: Optional[ast.AST], module: SourceModule
    ) -> Optional[str]:
        if ann is None:
            return None
        chain = attr_chain(ann)
        if not chain:
            if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
                cls = self._resolve_dotted_class(ann.value, module)
                return cls.full if cls else None
            return None
        cls = self._resolve_dotted_class(".".join(chain), module)
        return cls.full if cls else None

    def external_name(
        self, expr: ast.AST, scope: Optional[FunctionInfo], module: SourceModule
    ) -> Optional[str]:
        """Canonical dotted name of ``expr`` when its root is an imported
        module/name (``jnp.asarray`` → "jax.numpy.asarray"); None when the
        root is a local variable or unknown."""
        chain = attr_chain(expr)
        if not chain:
            return None
        root = chain[0]
        fn = scope
        while fn is not None:
            if root in fn.bound:
                return None  # a local variable, not an import
            fn = fn.parent
        target = self.imports.get(module.modname, {}).get(root)
        if target is None:
            # builtins (print/float/...) and module-level names
            return ".".join(chain) if len(chain) >= 1 else None
        return ".".join([target] + chain[1:])

    def resolve_name(
        self, name: str, scope: Optional[FunctionInfo], module: SourceModule
    ) -> List[FunctionInfo]:
        fn = scope
        while fn is not None:
            if name in fn.nested:
                return list(fn.nested[name])
            if name in fn.bound:
                return []  # shadowed by a non-function local
            fn = fn.parent
        mod_fn = self.module_functions.get(module.modname, {}).get(name)
        if mod_fn is not None:
            return [mod_fn]
        target = self.imports.get(module.modname, {}).get(name)
        if target:
            resolved = self._resolve_import_target(target)
            return [resolved] if resolved else []
        return []

    def returned_functions(self, fn: FunctionInfo) -> List[FunctionInfo]:
        """Nested defs a factory function returns (``def ring(...): ...;
        return ring``) — one extra hop for ``f = factory(); jax.jit(f)``."""
        out: List[FunctionInfo] = []
        for node in fn.body_nodes():
            if not isinstance(node, ast.Return) or node.value is None:
                continue
            if isinstance(node.value, ast.Name):
                out.extend(fn.nested.get(node.value.id, []))
            elif isinstance(node.value, ast.Lambda):
                for cand in self.functions:
                    if cand.module is fn.module and cand.node is node.value:
                        out.append(cand)
        return out

    def resolve_callable_deep(
        self, expr: ast.AST, scope: Optional[FunctionInfo], module: SourceModule
    ) -> List[FunctionInfo]:
        """`resolve_callable` plus two jit-site-only hops: unwrap
        ``partial(f, ...)`` and follow ``name = factory(...)`` to the
        factory's returned nested defs."""
        if (
            isinstance(expr, ast.Call)
            and self.external_name(expr.func, scope, module) in PARTIAL_NAMES
            and expr.args
        ):
            return self.resolve_callable_deep(expr.args[0], scope, module)
        direct = self.resolve_callable(expr, scope, module)
        if direct:
            return direct
        if isinstance(expr, ast.Name) and scope is not None:
            out: List[FunctionInfo] = []
            look = scope
            while look is not None:
                for node in look.body_nodes():
                    if not isinstance(node, ast.Assign):
                        continue
                    if not any(
                        isinstance(t, ast.Name) and t.id == expr.id
                        for t in node.targets
                    ):
                        continue
                    value = node.value
                    if (
                        isinstance(value, ast.Call)
                        and self.external_name(value.func, look, module)
                        in PARTIAL_NAMES
                        and value.args
                    ):
                        out.extend(
                            self.resolve_callable_deep(value.args[0], look, module)
                        )
                    elif isinstance(value, ast.Call):
                        for factory in self.resolve_callable(
                            value.func, look, module
                        ):
                            out.extend(self.returned_functions(factory))
                if out:
                    return out
                look = look.parent
        return []

    def resolve_method(self, class_full: str, method: str) -> List[FunctionInfo]:
        out = []
        for full in sorted(self.related_classes(class_full)):
            info = self.classes.get(full)
            if info and method in info.methods:
                out.append(info.methods[method])
        return out

    def resolve_callable(
        self, expr: ast.AST, scope: Optional[FunctionInfo], module: SourceModule
    ) -> List[FunctionInfo]:
        """Package-internal candidates for a call/reference expression."""
        if isinstance(expr, ast.Name):
            return self.resolve_name(expr.id, scope, module)
        chain = attr_chain(expr)
        if not chain:
            return []
        if chain[0] == "self" and scope is not None and len(chain) == 2:
            cls = self._enclosing_class(scope)
            if cls:
                return self.resolve_method(cls, chain[1])
            return []
        if len(chain) == 2 and scope is not None:
            # annotated local: method.loss with method: PPOConfig
            fn = scope
            while fn is not None:
                cls_full = fn.var_types.get(chain[0])
                if cls_full:
                    return self.resolve_method(cls_full, chain[1])
                if chain[0] in fn.bound:
                    break
                fn = fn.parent
        # module-alias chain: stats.whiten with `import ... as stats`
        root_target = None
        fn = scope
        shadowed = False
        while fn is not None:
            if chain[0] in fn.bound:
                shadowed = True
                break
            fn = fn.parent
        if not shadowed:
            root_target = self.imports.get(module.modname, {}).get(chain[0])
        if root_target:
            resolved = self._resolve_import_target(
                ".".join([root_target] + chain[1:])
            )
            return [resolved] if resolved else []
        return []

    def _enclosing_class(self, scope: FunctionInfo) -> Optional[str]:
        fn = scope
        while fn is not None:
            if fn.class_full:
                return fn.class_full
            fn = fn.parent
        return None

    # -- jit roots & reachability ----------------------------------------

    def is_jit_name(self, dotted: Optional[str]) -> bool:
        return dotted in JIT_WRAPPERS

    def jit_call(self, call: ast.Call, scope, mod) -> Optional[Tuple[str, Optional[ast.AST]]]:
        """``(wrapper's name, the expression of the function it traces)`` for a
        call that opens a trace (a ``JIT_WRAPPERS`` name, or the program
        store's constructors, whose function is the second argument), else
        ``None``; the expression is ``None`` where the call names no function."""
        chain = attr_chain(call.func) or []
        if chain[-1:] == ["stored_program"] or chain[-2:] == ["programs", "program"]:
            return STORED_JIT, call.args[1] if len(call.args) > 1 else None
        name = self.external_name(call.func, scope, mod)
        if not self.is_jit_name(name):
            return None
        return name, call.args[0] if call.args else None

    def _jit_kwargs(self, call: ast.Call) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        static = donate = ()
        for kw in call.keywords:
            if kw.arg in ("static_argnums", "static_argnames"):
                static = _int_tuple(kw.value) or (-1,)
            if kw.arg == "donate_argnums":
                donate = _int_tuple(kw.value)
        return static, donate

    def enclosing_function(
        self, module: SourceModule, node: ast.AST
    ) -> Optional[FunctionInfo]:
        """Innermost FunctionInfo whose own body contains ``node``."""
        module.build_parents()
        cur = module.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                for fn in self.functions:
                    if fn.module is module and fn.node is cur:
                        return fn
                return None
            cur = module.parents.get(cur)
        return None

    def _add_root(
        self,
        fn: FunctionInfo,
        wrapper: str,
        module: SourceModule,
        line: int,
        static: Tuple[int, ...],
        donate: Tuple[int, ...],
    ) -> None:
        self.jit_roots.append(
            JitRoot(fn, wrapper, module, line, static, donate)
        )

    def _collect_jit_roots(self) -> None:
        for mod in self.ctx.modules:
            for node in ast.walk(mod.tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    self._roots_from_decorators(mod, node)
                if not isinstance(node, ast.Call):
                    continue
                scope = self.enclosing_function(mod, node)
                name, target = self.jit_call(node, scope, mod) or (None, None)
                if target is None:
                    continue
                static, donate = self._jit_kwargs(node)
                if isinstance(target, ast.Lambda):
                    for fn in self.functions:
                        if fn.module is mod and fn.node is target:
                            self._add_root(fn, name, mod, node.lineno, static, donate)
                    continue
                for fn in self.resolve_callable_deep(target, scope, mod):
                    self._add_root(fn, name, mod, node.lineno, static, donate)

    def _roots_from_decorators(self, mod: SourceModule, node) -> None:
        for dec in node.decorator_list:
            scope = self.enclosing_function(mod, node)
            target = dec
            static = donate = ()
            if isinstance(dec, ast.Call):
                fname = self.external_name(dec.func, scope, mod)
                if fname in PARTIAL_NAMES and dec.args:
                    inner = dec.args[0]
                    if self.is_jit_name(self.external_name(inner, scope, mod)):
                        static, donate = self._jit_kwargs(dec)
                        target = inner
                    else:
                        continue
                elif self.is_jit_name(fname):
                    static, donate = self._jit_kwargs(dec)
                    target = dec.func
                else:
                    continue
            name = self.external_name(target, scope, mod)
            if not self.is_jit_name(name):
                continue
            for fn in self.functions:
                if fn.module is mod and fn.node is node:
                    self._add_root(fn, name, mod, node.lineno, static, donate)

    def edges(self, fn: FunctionInfo) -> List[FunctionInfo]:
        """Resolved intra-package callees + referenced package functions."""
        out: List[FunctionInfo] = []
        seen: Set[str] = set()
        for node in fn.body_nodes():
            exprs: List[ast.AST] = []
            if isinstance(node, ast.Call):
                exprs.append(node.func)
            elif isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
                exprs.append(node)
            for expr in exprs:
                for callee in self.resolve_callable(expr, fn, fn.module):
                    if callee.full not in seen:
                        seen.add(callee.full)
                        out.append(callee)
        # nested defs referenced by name count via the Name rule above;
        # decorator-jitted nested defs are roots on their own
        return out

    def reach_from(self, roots: List[FunctionInfo]) -> Dict[str, str]:
        """``FunctionInfo.full`` → root qualname for every function reachable
        from ``roots`` over the same edges jit tracing uses: resolved calls,
        bare package-function references (while_loop/scan/vmap bodies), and
        nested defs/lambdas. The generic engine behind jit-root tracing and
        the determinism pass's bit-equivalence-critical root set."""
        via: Dict[str, str] = {}
        work: List[FunctionInfo] = []
        for root in roots:
            if root.full not in via:
                via[root.full] = root.qualname
                work.append(root)
        while work:
            fn = work.pop()
            v = via[fn.full]
            callees = list(self.edges(fn))
            # nested defs/lambdas of reached code are part of the region even
            # when only ever passed by reference (while_loop/scan/vmap args)
            for group in fn.nested.values():
                callees.extend(group)
            for callee in callees:
                if callee.full not in via:
                    via[callee.full] = v
                    work.append(callee)
        return via

    def resolve_root_names(self, patterns) -> List[FunctionInfo]:
        """FunctionInfos matching registry patterns: a dotted pattern
        (``FileExperienceQueue.put``) matches the exact qualname or a
        ``.``-suffix of it; a bare name (``make_experience``) matches every
        function/method with that name, in any class. Used by passes that
        declare root sets by name (``analysis/determinism.py``)."""
        out: List[FunctionInfo] = []
        seen: Set[str] = set()
        for fn in self.functions:
            last = fn.qualname.rsplit(".", 1)[-1]
            for pat in patterns:
                if "." in pat:
                    hit = fn.qualname == pat or fn.qualname.endswith("." + pat)
                else:
                    hit = last == pat
                if hit and fn.full not in seen:
                    seen.add(fn.full)
                    out.append(fn)
                    break
        return out

    def _mark_traced(self) -> None:
        self.traced_via = self.reach_from([r.fn for r in self.jit_roots])
        self.traced = set(self.traced_via)

    def traced_functions(self) -> List[FunctionInfo]:
        return [fn for fn in self.functions if fn.full in self.traced]

    # -- thread roots & per-root reachability -----------------------------

    def _resolve_thread_target(
        self, expr: ast.AST, scope: Optional[FunctionInfo], mod: SourceModule
    ) -> List[FunctionInfo]:
        if isinstance(expr, ast.Lambda):
            return [fn for fn in self.functions if fn.module is mod and fn.node is expr]
        return self.resolve_callable_deep(expr, scope, mod)

    def _collect_thread_roots(self) -> None:
        seen: Set[Tuple[str, str]] = set()  # (full, via): one root per pair
        for mod in self.ctx.modules:
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                scope = self.enclosing_function(mod, node)
                target: Optional[ast.AST] = None
                via = None
                name = self.external_name(node.func, scope, mod)
                if name in THREAD_CONSTRUCTORS:
                    via = name.rsplit(".", 1)[-1]
                    for kw in node.keywords:
                        if kw.arg == "target":
                            target = kw.value
                elif (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "submit"
                    and node.args
                ):
                    # executor.submit(f, ...) / pipe.submit(work): the first
                    # positional arg runs on a worker thread
                    via = "submit"
                    target = node.args[0]
                if target is None:
                    continue
                for fn in self._resolve_thread_target(target, scope, mod):
                    if (fn.full, via) in seen:
                        continue
                    seen.add((fn.full, via))
                    self.thread_roots.append(
                        ThreadRoot(fn=fn, via=via, module=mod, line=node.lineno)
                    )
        # HTTP handler classes: each request's do_* dispatch runs on a
        # ThreadingHTTPServer per-connection thread — no Thread(...) call
        # exists to discover, the spawn is inside the stdlib
        for full in sorted(self.classes):
            if not self._is_http_handler(full):
                continue
            info = self.classes[full]
            for mname in sorted(info.methods):
                if not mname.startswith("do_"):
                    continue
                fn = info.methods[mname]
                if (fn.full, "http-handler") in seen:
                    continue
                seen.add((fn.full, "http-handler"))
                self.thread_roots.append(
                    ThreadRoot(
                        fn=fn,
                        via="http-handler",
                        module=info.module,
                        line=fn.node.lineno,
                    )
                )

    def _is_http_handler(self, class_full: str) -> bool:
        """Does ``class_full`` (or any package superclass of it) extend a
        stdlib HTTP/socketserver request-handler base?"""
        for full in self._closure(class_full, self._supers):
            info = self.classes.get(full)
            if info is None:
                continue
            for base in info.base_names:
                head, _, rest = base.partition(".")
                target = self.imports.get(info.module.modname, {}).get(head)
                canonical = (
                    (f"{target}.{rest}" if rest else target)
                    if target
                    else base
                )
                if canonical in HTTP_HANDLER_BASES:
                    return True
        return False

    def thread_membership(self) -> Dict[str, FrozenSet[str]]:
        """``FunctionInfo.full`` → the set of thread-root labels (root
        ``FunctionInfo.full``\\ s, plus the implicit ``"main"``) whose
        execution can reach the function. Functions not reachable from any
        spawned-thread root belong to ``"main"`` alone; a thread-reachable
        function that main-side code ALSO calls carries ``"main"`` *and*
        its thread labels, so a shared helper's accesses count on both
        sides of the escape check (a stats accumulator touched by the
        trainer loop and an actor worker is cross-thread, not
        worker-private).

        Reachability follows the same edges as jit-root tracing (resolved
        calls, bare function references, nested defs), so a thread target
        that fans out through ``self.m()`` dispatch or factory closures is
        followed the same way a jitted root is.
        """
        if self._thread_membership is not None:
            return self._thread_membership

        def reach(fn: FunctionInfo, seen: Set[str], skip: Set[str]) -> None:
            work = [fn]
            seen.add(fn.full)
            while work:
                cur = work.pop()
                callees = list(self.edges(cur))
                for group in cur.nested.values():
                    callees.extend(group)
                for callee in callees:
                    if callee.full not in seen and callee.full not in skip:
                        seen.add(callee.full)
                        work.append(callee)

        membership: Dict[str, Set[str]] = {}
        thread_reachable: Set[str] = set()
        root_fulls = {r.fn.full for r in self.thread_roots}
        for root in self.thread_roots:
            seen: Set[str] = set()
            reach(root.fn, seen, set())
            thread_reachable |= seen
            for full in seen:
                membership.setdefault(full, set()).add(root.fn.full)
        # main reaches everything not exclusively behind a spawn point:
        # BFS from every function outside the thread-reachable set re-adds
        # "main" to shared helpers main-side code also calls. The BFS never
        # descends INTO a thread-root function: the spawning frame holds a
        # bare reference to its target (`Thread(target=work)` is a Name
        # edge), and a spawn is not a main-side execution of the body.
        main_seen: Set[str] = set()
        for fn in self.functions:
            if fn.full not in thread_reachable and fn.full not in main_seen:
                reach(fn, main_seen, root_fulls)
        out: Dict[str, FrozenSet[str]] = {}
        for fn in self.functions:
            roots = set(membership.get(fn.full, ()))
            if fn.full in main_seen or not roots:
                roots.add("main")
            out[fn.full] = frozenset(roots)
        self._thread_membership = out
        return out


# ---------------------------------------------------------------------------
# exception-edge modeling (the ownership/lifecycle pass, analysis/ownership.py)
# ---------------------------------------------------------------------------


class ExceptionFlow:
    """Structural exception-edge facts for one function body.

    Python has two constructs that guarantee cleanup on EVERY exit —
    normal fall-through, early ``return``, and a raising statement:
    ``try/finally`` (the finalbody runs on all three) and ``with`` (the
    context manager's ``__exit__`` runs on all three). The ownership pass
    treats a resource released inside a covering finalbody — or acquired
    as a ``with`` context expression — as release-covered on all exits;
    everything else must be proven released path-by-path.
    """

    def __init__(self, fn: FunctionInfo):
        self.fn = fn
        self.fn.module.build_parents()

    def covering_finallys(self, node: ast.AST) -> List[ast.Try]:
        """Innermost-first ``try`` statements (within this function) whose
        TRY BODY contains ``node`` and which carry a ``finally`` — the
        finalbodies that execute on every exception edge crossing
        ``node``'s position. Handler and finalbody positions themselves are
        NOT covered (an exception there escapes the same try)."""
        out: List[ast.Try] = []
        mod = self.fn.module
        cur: Optional[ast.AST] = node
        while cur is not None and cur is not self.fn.node:
            parent = mod.parents.get(cur)
            if (
                isinstance(parent, ast.Try)
                and parent.finalbody
                and cur in parent.body
            ):
                out.append(parent)
            cur = parent
        return out

    def in_excepthandler(self, node: ast.AST) -> bool:
        """Is ``node`` inside an ``except`` handler body of this function?
        Releases there cover only the exception edge, not the normal path —
        the pass must not treat them as the main-path release."""
        mod = self.fn.module
        cur: Optional[ast.AST] = node
        while cur is not None and cur is not self.fn.node:
            if isinstance(cur, ast.ExceptHandler):
                return True
            cur = mod.parents.get(cur)
        return False

    def with_context_calls(self) -> Set[int]:
        """``id()`` of every Call node used as a ``with`` context expression
        in this function's own body — an acquire spelled that way is
        release-covered by the context manager's ``__exit__`` on all
        exits (``with tracer.span(...):``)."""
        out: Set[int] = set()
        for node in self.fn.body_nodes():
            if not isinstance(node, (ast.With, ast.AsyncWith)):
                continue
            for item in node.items:
                expr = item.context_expr
                if isinstance(expr, ast.Call):
                    out.add(id(expr))
        return out
