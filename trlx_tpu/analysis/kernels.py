"""Pallas kernel-discipline passes (GL1001-GL1004).

Everything that landed with the native-kernel PRs is guarded by
*convention*: every ``pallas_call`` hides behind the shared
``ops/pallas_utils.py`` gate (``resolve_interpret()`` selects interpret
mode off-TPU; a ``has_pallas_tpu()`` probe, which this tree no longer
carries, counts as a gate too), every ``*_pallas`` metric gauge is
stamped from the state that selected the compute (an artifact must not
claim kernel=1 unconditionally — a bug that shipped twice), every kernel body obeys the documented lowering
landmines, and every kernel flavor has an XLA reference pinned
bit-identical by a parity test. This pass family turns each convention
into a whole-program check (docs/STATIC_ANALYSIS.md, "The kernel
discipline contract"):

- **GL1001 — fallback-gate integrity.** A ``pallas_call`` site must not
  be reachable from an entry point without crossing a function that
  consults the shared gate (a call resolving to
  ``pallas_utils.has_pallas_tpu`` / ``resolve_interpret`` /
  ``default_interpret``). The walk goes UP the caller graph from the
  site's enclosing function; ``custom_vjp`` fwd/bwd rules — which have
  no syntactic caller — are stitched to their primal via module-level
  ``X.defvjp(fwd, bwd)`` statements, so ``_flash_bwd_rule`` inherits
  ``flash_attention``'s gate instead of looking like an ungated root.

- **GL1002 — gauge-stamp discipline.** Any store whose key/attribute
  name ends in ``_pallas`` (subscript store, dict literal entry,
  attribute assignment, keyword argument) must not be a truthy literal,
  even wrapped in ``float()``/``bool()``/``asarray()``. Values derived
  from ``has_pallas_tpu()`` (or any non-literal expression) pass; falsy
  literals pass too — a ``False`` default is the pre-gate placeholder,
  and the bug class is exactly "claims kernel=1 unconditionally".

- **GL1003 — kernel-body purity.** Functions passed to ``pallas_call``
  (resolved through the ``functools.partial`` / local-assignment
  machinery the jit-root tracer uses) and ``BlockSpec`` index maps must
  not call host-sync / wall-clock / global-RNG primitives, and must not
  close over a name bound to a concrete ndarray constructor
  (``np.asarray(...)`` et al.) — a captured array constant-folds into
  the lowered program and fakes 1-ulp parity (lowering landmine #4).
  Closing over scalars/ints (block shapes, head counts) is fine; index
  maps stay pure over grid indices + scalar-prefetch refs.

- **GL1004 — parity-coverage registry.** :data:`KERNEL_PARITY` names
  each kernel flavor, its entry point, its XLA reference, and the test
  file pinning bit-parity (the ``RANK_UNIFORM_FIELDS`` pattern: the
  registry IS the justification mechanism, so a GL1004 finding should
  almost never be baselined). A ``pallas_call`` site with no registered
  entry in its upward caller closure is a finding; a registered entry
  whose reference no longer resolves, or whose parity test file is
  gone, is a finding. Growing the kernel surface means growing the
  registry — and the parity suite — in the same PR.

Like every graftlint module this file is stdlib-only: it must import
(and run) in the jax-free CI lint job.
"""

import ast
import os
from typing import Dict, List, Optional, Set, Tuple

from trlx_tpu.analysis.callgraph import (
    CallGraph,
    FunctionInfo,
    attr_chain,
)
from trlx_tpu.analysis.core import (
    AnalysisContext,
    Finding,
    LintPass,
    SourceModule,
    register_pass,
)

__all__ = ["KernelDisciplinePass", "KERNEL_PARITY"]


# ---------------------------------------------------------------------------
# the parity registry (GL1004)
# ---------------------------------------------------------------------------

# (flavor, entry point, XLA reference, parity test file) — one row per
# kernel flavor shipped in ops/. The entry point is the function a
# pallas_call site must reach in its upward caller closure; the reference
# is the staged-XLA oracle the parity test pins the kernel against; the
# test path is relative to the repo root. Registering a flavor here is a
# CONTRACT: the reference stays callable and the test file keeps pinning
# bit-equality (docs/STATIC_ANALYSIS.md, "The kernel discipline
# contract").
KERNEL_PARITY: Tuple[Tuple[str, str, str, str], ...] = (
    # in-place paged decode attention (PR 12)
    ("paged-decode", "paged_attention_decode",
     "paged_attention_decode_reference", "tests/test_paged_attention.py"),
    # chunked paged prefill (PR 13)
    ("paged-prefill", "paged_prefill_attention",
     "paged_prefill_attention_reference", "tests/test_paged_attention.py"),
    # multi-position speculative verify — deliberately DELEGATES to the
    # prefill kernel body (one grid, one op sequence); the flavor is
    # registered separately because it has its own entry seam and its own
    # parity pin (the spec-engine acceptance suite)
    ("paged-verify", "paged_verify_attention",
     "paged_prefill_attention_reference", "tests/test_spec_engine.py"),
    # fused temperature/top-k/top-p sampling (PR 16)
    ("fused-sample", "fused_sample",
     "sample_token_from_logits", "tests/test_paged_attention.py"),
    # flash attention forward (PR 16)
    ("flash-fwd", "flash_attention",
     "attention_reference", "tests/test_flash_attention.py"),
    # flash attention fused backward (dq+dk+dv)
    ("flash-bwd", "flash_attention_bwd_chunk",
     "attention_reference", "tests/test_flash_attention.py"),
    # the chunked gated delta rule's forward (Kimi Delta Attention, PR 55);
    # its backward differentiates the reference
    ("kda-scan", "kda_chunked",
     "kda_chunked_reference", "tests/test_delta_rule_kernel.py"),
)


# ---------------------------------------------------------------------------
# name classifiers
# ---------------------------------------------------------------------------

# the shared fallback gate: any call resolving (through import aliases)
# to one of these marks its enclosing function gate-bearing. Matching on
# the trailing ``pallas_utils.<fn>`` keeps fixtures honest: a mini-tree
# must route through a module NAMED pallas_utils, same as the real ops/.
_GATE_FNS = ("has_pallas_tpu", "resolve_interpret", "default_interpret")


def _is_gate_name(name: Optional[str]) -> bool:
    if not name:
        return False
    parts = name.split(".")
    return (
        len(parts) >= 2
        and parts[-1] in _GATE_FNS
        and parts[-2] == "pallas_utils"
    )


def _is_pallas_call_name(name: Optional[str]) -> bool:
    if not name:
        return False
    return name == "pallas_call" or name.endswith(".pallas_call")


def _is_block_spec_name(name: Optional[str]) -> bool:
    if not name:
        return False
    return name == "BlockSpec" or name.endswith(".BlockSpec")


# wall-clock / RNG / host-sync primitives a kernel body must never call:
# the body is traced once at lowering time, so a host read bakes a
# constant into the program (and differs between lowerings)
_IMPURE_CALLS = frozenset({
    "time.time", "time.time_ns", "time.monotonic", "time.monotonic_ns",
    "time.perf_counter", "time.perf_counter_ns",
    "datetime.datetime.now", "datetime.datetime.utcnow",
    "datetime.date.today",
    "jax.device_get",
    "numpy.asarray", "numpy.array", "numpy.frombuffer",
    "print", "input",
})
_IMPURE_PREFIXES = ("random.", "numpy.random.")
_IMPURE_METHODS = frozenset({"item", "tolist", "block_until_ready"})

# array constructors whose result, captured by a kernel closure, becomes
# a folded constant in the lowered program (lowering landmine #4)
_ARRAY_CONSTRUCTORS = frozenset({
    "numpy.asarray", "numpy.array", "numpy.arange", "numpy.zeros",
    "numpy.ones", "numpy.full", "numpy.linspace", "numpy.eye",
    "jax.numpy.asarray", "jax.numpy.array", "jax.numpy.arange",
    "jax.numpy.zeros", "jax.numpy.ones", "jax.numpy.full",
    "jax.numpy.linspace", "jax.numpy.eye",
})

# literal-unwrapping for GL1002: `float(True)` / `jnp.asarray(1.0)` /
# `np.float32(1)` still stamp a literal
_WRAPPER_FNS = frozenset({"float", "int", "bool", "round", "abs"})
_WRAPPER_METHODS = frozenset({
    "asarray", "array", "float32", "float64", "int32", "int64", "bool_",
})


def _literal_stamp(value: ast.AST) -> Optional[bool]:
    """Truthiness of ``value`` when it is a (possibly wrapped) bool/int/
    float literal; None for any non-literal expression."""
    node = value
    while isinstance(node, ast.Call) and node.args:
        f = node.func
        if isinstance(f, ast.Name) and f.id in _WRAPPER_FNS:
            node = node.args[0]
        elif isinstance(f, ast.Attribute) and f.attr in _WRAPPER_METHODS:
            node = node.args[0]
        else:
            break
    if isinstance(node, ast.Constant) and isinstance(
        node.value, (bool, int, float)
    ):
        return bool(node.value)
    return None


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------


class _Site:
    """One ``pallas_call`` call site."""

    def __init__(
        self,
        call: ast.Call,
        mod: SourceModule,
        fn: Optional[FunctionInfo],
    ):
        self.call = call
        self.mod = mod
        self.fn = fn  # enclosing function (None: module level)


@register_pass
class KernelDisciplinePass(LintPass):
    name = "kernel-discipline"
    codes = ("GL1001", "GL1002", "GL1003", "GL1004")
    description = (
        "Pallas kernel discipline: fallback-gate reachability, *_pallas "
        "gauge stamps, kernel-body purity, parity-registry coverage"
    )

    def run(self, ctx: AnalysisContext) -> List[Finding]:
        graph = ctx.callgraph
        findings: List[Finding] = []
        findings.extend(self._check_gauge_stamps(graph))
        sites = self._collect_sites(graph)
        if sites:
            callers = self._caller_map(graph)
            gated = self._gate_bearing(graph)
            findings.extend(self._check_gates(sites, callers, gated))
            findings.extend(self._check_purity(graph, sites))
            findings.extend(self._check_registry(ctx, graph, sites, callers))
        else:
            findings.extend(self._check_registry(ctx, graph, [], {}))
        findings.sort(key=lambda f: (f.path, f.line, f.code, f.detail))
        return findings

    # -- shared graph views ----------------------------------------------

    def _collect_sites(self, graph: CallGraph) -> List[_Site]:
        sites: List[_Site] = []
        for mod in graph.ctx.modules:
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                scope = graph.enclosing_function(mod, node)
                if _is_pallas_call_name(
                    graph.external_name(node.func, scope, mod)
                ):
                    sites.append(_Site(node, mod, scope))
        return sites

    def _caller_map(self, graph: CallGraph) -> Dict[str, List[FunctionInfo]]:
        """Reverse adjacency over the same edges jit tracing uses, plus
        two synthetic rules: a parent function "calls" its nested defs
        (the parent frame is the only way control reaches them), and a
        ``custom_vjp`` primal "calls" the fwd/bwd rules registered by a
        ``X.defvjp(fwd, bwd)`` statement — the rules have no syntactic
        caller, but execute exactly when the primal's callers do."""
        callers: Dict[str, List[FunctionInfo]] = {}
        seen: Set[Tuple[str, str]] = set()

        def add(callee: FunctionInfo, caller: FunctionInfo) -> None:
            if (callee.full, caller.full) in seen:
                return
            seen.add((callee.full, caller.full))
            callers.setdefault(callee.full, []).append(caller)

        for fn in graph.functions:
            for callee in graph.edges(fn):
                add(callee, fn)
            for group in fn.nested.values():
                for nested in group:
                    add(nested, fn)
        for mod in graph.ctx.modules:
            for node in ast.walk(mod.tree):
                if not (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "defvjp"
                    and len(node.args) >= 2
                ):
                    continue
                scope = graph.enclosing_function(mod, node)
                primals = graph.resolve_callable(node.func.value, scope, mod)
                if not primals:
                    continue
                for arg in node.args[:2]:
                    for rule in graph.resolve_callable_deep(arg, scope, mod):
                        for primal in primals:
                            add(rule, primal)
        return callers

    def _gate_bearing(self, graph: CallGraph) -> Set[str]:
        """``FunctionInfo.full`` of every function whose own body calls
        the shared pallas_utils gate."""
        out: Set[str] = set()
        for fn in graph.functions:
            for node in fn.body_nodes():
                if isinstance(node, ast.Call) and _is_gate_name(
                    graph.external_name(node.func, fn, fn.module)
                ):
                    out.add(fn.full)
                    break
        return out

    def _upward_closure(
        self,
        start: FunctionInfo,
        callers: Dict[str, List[FunctionInfo]],
    ) -> List[FunctionInfo]:
        """Every function from which ``start`` is reachable (including
        ``start``), over the caller map — gate-bearing or not."""
        out: List[FunctionInfo] = []
        seen: Set[str] = set()
        work = [start]
        while work:
            fn = work.pop()
            if fn.full in seen:
                continue
            seen.add(fn.full)
            out.append(fn)
            work.extend(callers.get(fn.full, ()))
        return out

    # -- GL1001: fallback-gate integrity ----------------------------------

    def _check_gates(
        self,
        sites: List[_Site],
        callers: Dict[str, List[FunctionInfo]],
        gated: Set[str],
    ) -> List[Finding]:
        findings: List[Finding] = []
        for site in sites:
            if site.fn is None:
                findings.append(
                    Finding(
                        code="GL1001",
                        path=site.mod.relpath,
                        line=site.call.lineno,
                        symbol="<module>",
                        detail="<module>",
                        message="module-level `pallas_call` runs at import "
                        "time with no fallback gate — wrap it in an entry "
                        "function that consults "
                        "`pallas_utils.has_pallas_tpu()` (docs/"
                        "STATIC_ANALYSIS.md, kernel discipline contract)",
                    )
                )
                continue
            # BFS up the caller graph; a branch crossing a gate-bearing
            # function is safe, a root reached with no gate on the path
            # is an ungated entry
            ungated: Set[str] = set()
            seen: Set[str] = set()
            work = [site.fn]
            while work:
                fn = work.pop()
                if fn.full in seen:
                    continue
                seen.add(fn.full)
                if fn.full in gated:
                    continue
                ups = callers.get(fn.full, ())
                if not ups:
                    ungated.add(fn.qualname)
                    continue
                work.extend(ups)
            for entry in sorted(ungated):
                findings.append(
                    Finding(
                        code="GL1001",
                        path=site.mod.relpath,
                        line=site.call.lineno,
                        symbol=site.fn.qualname,
                        detail=entry,
                        message=f"`pallas_call` in `{site.fn.qualname}` is "
                        f"reachable from entry `{entry}` without crossing "
                        "the shared fallback gate (`pallas_utils."
                        "has_pallas_tpu()` / `resolve_interpret()`): a "
                        "Mosaic-less build takes this path straight into a "
                        "TPU-only lowering — route the kernel-selecting "
                        "branch through the gate, or gate the entry itself",
                    )
                )
        return findings

    # -- GL1002: gauge-stamp discipline -----------------------------------

    def _check_gauge_stamps(self, graph: CallGraph) -> List[Finding]:
        findings: List[Finding] = []
        for mod in graph.ctx.modules:
            for node in ast.walk(mod.tree):
                for gauge, value in self._pallas_stamps(node):
                    if _literal_stamp(value) is not True:
                        continue
                    scope = graph.enclosing_function(mod, value)
                    findings.append(
                        Finding(
                            code="GL1002",
                            path=mod.relpath,
                            line=value.lineno,
                            symbol=scope.qualname if scope else "<module>",
                            detail=gauge,
                            message=f"`{gauge}` is stamped from a truthy "
                            "literal: a build without the Mosaic backend "
                            "would still claim kernel=1 in the artifact "
                            "(the twice-shipped fallback-gauge bug) — "
                            "derive the value from `pallas_utils."
                            "has_pallas_tpu()` instead",
                        )
                    )
        return findings

    def _pallas_stamps(
        self, node: ast.AST
    ) -> List[Tuple[str, ast.AST]]:
        """(gauge name, value expr) for every ``*_pallas`` store in
        ``node``: subscript stores with a literal string key, attribute
        assignments, dict-literal entries, and keyword arguments.
        ``AnnAssign`` field declarations are exempt — a dataclass default
        is the pre-gate placeholder, not a stamp (and must be falsy to
        pass the literal check anyway)."""
        out: List[Tuple[str, ast.AST]] = []
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if (
                    isinstance(t, ast.Subscript)
                    and isinstance(t.slice, ast.Constant)
                    and isinstance(t.slice.value, str)
                    and t.slice.value.endswith("_pallas")
                ):
                    out.append((t.slice.value, node.value))
                elif isinstance(t, ast.Attribute) and t.attr.endswith(
                    "_pallas"
                ):
                    out.append((t.attr, node.value))
        elif isinstance(node, ast.Dict):
            for key, value in zip(node.keys, node.values):
                if (
                    isinstance(key, ast.Constant)
                    and isinstance(key.value, str)
                    and key.value.endswith("_pallas")
                ):
                    out.append((key.value, value))
        elif isinstance(node, ast.Call):
            for kw in node.keywords:
                if kw.arg and kw.arg.endswith("_pallas"):
                    out.append((kw.arg, kw.value))
        return out

    # -- GL1003: kernel-body purity ---------------------------------------

    def _check_purity(
        self, graph: CallGraph, sites: List[_Site]
    ) -> List[Finding]:
        findings: List[Finding] = []
        checked: Set[str] = set()

        def check(fn: FunctionInfo, kind: str) -> None:
            if fn.full in checked:
                return
            checked.add(fn.full)
            findings.extend(self._purity_of(graph, fn, kind))

        for site in sites:
            if not site.call.args:
                continue
            for fn in graph.resolve_callable_deep(
                site.call.args[0], site.fn, site.mod
            ):
                check(fn, "kernel")
        # index maps: the 2nd positional arg / index_map= of every
        # BlockSpec in the tree (grid-spec factories build them far from
        # the pallas_call site, so scope is package-wide)
        for mod in graph.ctx.modules:
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                scope = graph.enclosing_function(mod, node)
                if not _is_block_spec_name(
                    graph.external_name(node.func, scope, mod)
                ):
                    continue
                exprs: List[ast.AST] = []
                if len(node.args) >= 2:
                    exprs.append(node.args[1])
                for kw in node.keywords:
                    if kw.arg == "index_map":
                        exprs.append(kw.value)
                for expr in exprs:
                    if isinstance(expr, ast.Lambda):
                        for fn in graph.functions:
                            if fn.module is mod and fn.node is expr:
                                check(fn, "index map")
                    else:
                        for fn in graph.resolve_callable_deep(
                            expr, scope, mod
                        ):
                            check(fn, "index map")
        return findings

    def _purity_of(
        self, graph: CallGraph, fn: FunctionInfo, kind: str
    ) -> List[Finding]:
        findings: List[Finding] = []
        seen: Set[str] = set()

        def emit(line: int, detail: str, what: str) -> None:
            if detail in seen:
                return
            seen.add(detail)
            findings.append(
                Finding(
                    code="GL1003",
                    path=fn.module.relpath,
                    line=line,
                    symbol=fn.qualname,
                    detail=detail,
                    message=f"{kind} `{fn.qualname}` {what} — the body is "
                    "traced once at lowering time, so host state bakes "
                    "into the program as a constant (lowering landmine: "
                    "constant folding fakes parity; docs/STATIC_ANALYSIS"
                    ".md, kernel discipline contract)",
                )
            )

        for node in fn.body_nodes():
            if isinstance(node, ast.Call):
                name = graph.external_name(node.func, fn, fn.module)
                if name in _IMPURE_CALLS or (
                    name
                    and name.startswith(_IMPURE_PREFIXES)
                ):
                    emit(node.lineno, name, f"calls host primitive `{name}()`")
                elif (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in _IMPURE_METHODS
                ):
                    emit(
                        node.lineno,
                        f".{node.func.attr}",
                        f"calls host-sync method `.{node.func.attr}()`",
                    )
            elif isinstance(node, ast.Name) and isinstance(
                node.ctx, ast.Load
            ):
                binding = self._ndarray_binding(graph, fn, node.id)
                if binding is not None:
                    emit(
                        node.lineno,
                        node.id,
                        f"closes over `{node.id}`, bound to a concrete "
                        f"ndarray (`{binding}`)",
                    )
        return findings

    def _ndarray_binding(
        self, graph: CallGraph, fn: FunctionInfo, name: str
    ) -> Optional[str]:
        """Canonical constructor name when free-variable ``name``, looked
        up through the enclosing scopes then module level, is bound to an
        array-constructor call in the same module; None otherwise
        (locals, params, scalars, imported names)."""
        if name in fn.bound:
            return None  # a local/param of the kernel itself

        def ctor_of(stmts, scope) -> Optional[str]:
            hit = None
            for node in stmts:
                if not isinstance(node, ast.Assign):
                    continue
                if not any(
                    isinstance(t, ast.Name) and t.id == name
                    for t in node.targets
                ):
                    continue
                value = node.value
                cname = (
                    graph.external_name(value.func, scope, fn.module)
                    if isinstance(value, ast.Call)
                    else None
                )
                # every binding must be an array ctor: a rebind to a
                # scalar (or anything else) clears the verdict
                hit = cname if cname in _ARRAY_CONSTRUCTORS else None
                if hit is None:
                    return None
            return hit

        look = fn.parent
        while look is not None:
            if name in look.bound:
                return ctor_of(look.body_nodes(), look)
            look = look.parent
        if name in graph.imports.get(fn.module.modname, {}):
            return None  # imported name: resolved elsewhere, not a capture
        return ctor_of(fn.module.tree.body, None)

    # -- GL1004: parity-coverage registry ---------------------------------

    def _check_registry(
        self,
        ctx: AnalysisContext,
        graph: CallGraph,
        sites: List[_Site],
        callers: Dict[str, List[FunctionInfo]],
    ) -> List[Finding]:
        findings: List[Finding] = []
        entries = {entry for _, entry, _, _ in KERNEL_PARITY}
        # (a) every pallas_call site reaches a registered entry upward
        for site in sites:
            covered = False
            if site.fn is not None:
                for fn in self._upward_closure(site.fn, callers):
                    if fn.qualname.rsplit(".", 1)[-1] in entries:
                        covered = True
                        break
            if not covered:
                symbol = site.fn.qualname if site.fn else "<module>"
                findings.append(
                    Finding(
                        code="GL1004",
                        path=site.mod.relpath,
                        line=site.call.lineno,
                        symbol=symbol,
                        detail=symbol,
                        message=f"`pallas_call` in `{symbol}` reaches no "
                        "entry registered in KERNEL_PARITY (analysis/"
                        "kernels.py): a kernel flavor without a pinned "
                        "XLA reference has no bit-parity story — add the "
                        "flavor (entry, reference, parity test) to the "
                        "registry AND the parity suite in the same PR",
                    )
                )
        # (b) registered flavors present in this tree keep their
        # reference and their parity test. Entries that do not resolve
        # here are someone else's tree (fixture mini-packages, the
        # scripts/ root) — vacuous by design, like DeterminismPass roots.
        for flavor, entry, reference, test_path in KERNEL_PARITY:
            entry_fns = graph.resolve_root_names([entry])
            if not entry_fns:
                continue
            fn = entry_fns[0]
            if not graph.resolve_root_names([reference]):
                findings.append(
                    Finding(
                        code="GL1004",
                        path=fn.module.relpath,
                        line=fn.node.lineno,
                        symbol=fn.qualname,
                        detail=f"{flavor}:reference:{reference}",
                        message=f"KERNEL_PARITY flavor `{flavor}` names "
                        f"reference `{reference}`, which no longer "
                        "resolves in the tree — the kernel lost its XLA "
                        "oracle; restore the reference or re-register "
                        "the flavor",
                    )
                )
            if not os.path.exists(os.path.join(ctx.base, test_path)):
                findings.append(
                    Finding(
                        code="GL1004",
                        path=fn.module.relpath,
                        line=fn.node.lineno,
                        symbol=fn.qualname,
                        detail=f"{flavor}:test:{test_path}",
                        message=f"KERNEL_PARITY flavor `{flavor}` pins "
                        f"bit-parity in `{test_path}`, which does not "
                        "exist — the flavor lost its parity test root; "
                        "restore the test or re-register the flavor",
                    )
                )
        return findings
