"""SPMD collective-discipline pass (GL7xx): host collectives must be
posted by EVERY rank, in the same order, with matching payloads — or the
pod hangs. ``ClusterDesyncError`` catches one class of divergence at
runtime, after the pod's time is already burning; this pass proves the
classic divergence shapes absent statically.

**The catalog.** A *direct collective site* is a call whose callee name
ends in ``process_allgather`` / ``sync_global_devices`` /
``broadcast_one_to_all`` (``jax.experimental.multihost_utils`` — the gloo
host collectives every multihost path here rides, including the telemetry
beat). A function is *collective-bearing* when a collective site is
reachable from it over the call graph (so ``save_state`` is bearing via
its nested ``commit``'s ``_commit_barrier``, and ``ClusterTelemetry.beat``
via ``_default_allgather``).

**The codes.**

- GL701 — a collective (or collective-bearing call) reachable only under a
  **rank-dependent branch**: an ``if`` whose test calls
  ``process_index()``, calls a package *rank predicate* (a function whose
  return value derives from ``process_index()``, e.g. ``_is_primary``), or
  tests a local assigned from either. Ranks outside the branch never post
  the collective ⇒ the ranks inside hang. The legitimate pattern — rank 0
  authors host-side files while the *barrier stays outside the guard* —
  does not fire, because the collective itself is unguarded.
- GL702 — a **direct** collective inside a loop whose trip count is not
  provably rank-uniform: ``while`` loops with a non-literal condition, and
  ``for`` loops over anything but ``range()`` of constants / config
  attribute chains / literal sequences. One extra iteration on one rank is
  one unmatched collective: the pod hangs at the loop exit.
- GL703 — the same **barrier-name literal** passed to
  ``sync_global_devices`` (or a package wrapper that forwards its
  parameter into it) at more than one call site: jax pairs barriers by
  name, so two sites sharing a literal can pair rank A's site-1 with rank
  B's site-2 and desynchronize both. Parameterized names (f-strings,
  wrapper parameters) are the fix and are out of scope.
- GL704 — a collective (or bearing call) gated on a **config field** that
  is not registered rank-uniform (:data:`RANK_UNIFORM_FIELDS`). Config is
  normally identical across ranks, but nothing enforces it; fields that
  gate collectives are a contract and must be documented as such
  (docs/STATIC_ANALYSIS.md "The rank-uniformity contract").

Known limits (documented, deliberate): bearing-ness does not flow through
values (a collective closure stored in a module global and invoked later —
``wait_for_saves``'s deferred commit — is invisible); long attribute
chains (``self.obs.cluster.beat``) don't resolve, mirroring the call
graph's limits; rank-dependence through data (a per-rank flag allgathered
elsewhere) is out of scope.
"""

import ast
from typing import Dict, List, Optional, Set, Tuple

from trlx_tpu.analysis.callgraph import CallGraph, FunctionInfo, attr_chain
from trlx_tpu.analysis.core import (
    AnalysisContext,
    Finding,
    LintPass,
    SourceModule,
    register_pass,
)

__all__ = ["CollectiveDisciplinePass", "RANK_UNIFORM_FIELDS"]

# host-collective callee names (attribute or bare): the gloo collectives
# every multihost path in this package posts
COLLECTIVE_NAMES = frozenset({
    "process_allgather",
    "sync_global_devices",
    "broadcast_one_to_all",
})

# Config fields DOCUMENTED as rank-uniform (the rank-uniformity contract,
# docs/STATIC_ANALYSIS.md): launchers must hand every rank the same value,
# because these fields gate whether a collective is posted at all. Gating
# a collective on any OTHER field is GL704 until the field is added here
# WITH a matching docs entry.
RANK_UNIFORM_FIELDS = frozenset({
    # resilience: gates the per-boundary preemption/telemetry allgather
    "coordinate_preemption",
    # resilience: gates the collective Orbax restore path on topology change
    "elastic",
    # train: gate interval/eval/best checkpoints — every checkpoint is a
    # collective Orbax shard write plus commit barriers, so every rank must
    # take the same save decision at the same boundary
    "checkpoint_interval",
    "eval_interval",
    "save_best",
    # async_rl: the fleet transport selection and its tree fanout. The
    # collective fleet's membership gauges ride the telemetry-beat
    # allgather's packed vector, and the coordinator/endpoint is authored
    # once per fleet — learner ranks disagreeing on the transport (or its
    # tree shape) would build mismatched fleets around the same beat
    # (docs/ASYNC_RL.md "Transports", docs/STATIC_ANALYSIS.md)
    "transport",
    "fanout",
})


def _is_terminal(stmt: ast.stmt) -> bool:
    """Statement unconditionally leaves the enclosing body."""
    if isinstance(stmt, (ast.Return, ast.Raise, ast.Continue, ast.Break)):
        return True
    if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Call):
        # sys.exit(...) — close enough for a linter
        chain = attr_chain(stmt.value.func)
        return bool(chain) and chain[-1] == "exit"
    return False


def _body_is_terminal(body: List[ast.stmt]) -> bool:
    return bool(body) and _is_terminal(body[-1])


class _RankDependence:
    """Per-function rank-dependence facts: which expressions/locals derive
    from ``process_index()``."""

    def __init__(self, graph: CallGraph, predicates: Set[str]):
        self.graph = graph
        self.predicates = predicates  # FunctionInfo.full of rank predicates

    def expr_is_rank_dependent(
        self, expr: ast.AST, fn: Optional[FunctionInfo], mod: SourceModule,
        local_ranky: Set[str],
    ) -> bool:
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Call):
                chain = attr_chain(sub.func)
                if chain and chain[-1] == "process_index":
                    return True
                for callee in self.graph.resolve_callable(sub.func, fn, mod):
                    if callee.full in self.predicates:
                        return True
            elif isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load):
                if sub.id in local_ranky:
                    return True
        return False

    def local_rank_names(
        self, fn: FunctionInfo
    ) -> Set[str]:
        """Locals assigned from a rank-dependent expression in ``fn``."""
        out: Set[str] = set()
        # two sweeps: a name assigned from another ranky name still resolves
        for _ in range(2):
            for node in fn.body_nodes():
                if not isinstance(node, ast.Assign):
                    continue
                if not self.expr_is_rank_dependent(
                    node.value, fn, fn.module, out
                ):
                    continue
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out.add(t.id)
        return out


@register_pass
class CollectiveDisciplinePass(LintPass):
    name = "collective-discipline"
    codes = ("GL701", "GL702", "GL703", "GL704")
    description = "SPMD host collectives posted divergently across ranks"

    def run(self, ctx: AnalysisContext) -> List[Finding]:
        graph = ctx.callgraph
        direct = self._direct_sites(graph)
        if not direct:
            return []
        bearing = self._bearing_closure(graph, direct)
        predicates = self._rank_predicates(graph)
        rank = _RankDependence(graph, predicates)
        findings: List[Finding] = []
        findings.extend(self._check_guards(graph, direct, bearing, rank))
        findings.extend(self._check_loops(graph, direct))
        findings.extend(self._check_barrier_names(graph, direct))
        findings.sort(key=lambda f: (f.path, f.line, f.code))
        return findings

    # -- the catalog ------------------------------------------------------

    def _direct_sites(
        self, graph: CallGraph
    ) -> List[Tuple[SourceModule, ast.Call, Optional[FunctionInfo], str]]:
        out = []
        for mod in graph.ctx.modules:
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                chain = attr_chain(node.func)
                if not chain or chain[-1] not in COLLECTIVE_NAMES:
                    continue
                scope = graph.enclosing_function(mod, node)
                out.append((mod, node, scope, chain[-1]))
        return out

    def _bearing_closure(self, graph: CallGraph, direct) -> Set[str]:
        """FunctionInfo.full of every function from which a collective call
        site is reachable (callee fixed point; nested defs count as their
        own functions but are referenced by name, so edges cover them)."""
        bearing: Set[str] = set()
        for _mod, _node, scope, _name in direct:
            if scope is not None:
                bearing.add(scope.full)
        changed = True
        while changed:
            changed = False
            for fn in graph.functions:
                if fn.full in bearing:
                    continue
                callees = list(graph.edges(fn))
                if any(c.full in bearing for c in callees):
                    bearing.add(fn.full)
                    changed = True
        return bearing

    def _rank_predicates(self, graph: CallGraph) -> Set[str]:
        """Functions whose return value derives from ``process_index()``
        (``_is_primary``-style predicates), transitively."""
        out: Set[str] = set()
        changed = True
        while changed:
            changed = False
            for fn in graph.functions:
                if fn.full in out:
                    continue
                for node in fn.body_nodes():
                    if not isinstance(node, ast.Return) or node.value is None:
                        continue
                    hit = False
                    for sub in ast.walk(node.value):
                        if isinstance(sub, ast.Call):
                            chain = attr_chain(sub.func)
                            if chain and chain[-1] == "process_index":
                                hit = True
                            else:
                                for callee in graph.resolve_callable(
                                    sub.func, fn, fn.module
                                ):
                                    if callee.full in out:
                                        hit = True
                    if hit:
                        out.add(fn.full)
                        changed = True
                        break
        return out

    # -- GL701 / GL704: rank- and config-gated collectives ----------------

    def _collective_calls_in(
        self, graph: CallGraph, fn: FunctionInfo, bearing: Set[str]
    ) -> List[Tuple[ast.Call, str]]:
        """(call node, label) for direct collectives and bearing-callee
        calls in ``fn``'s own body."""
        out: List[Tuple[ast.Call, str]] = []
        for node in fn.body_nodes():
            if not isinstance(node, ast.Call):
                continue
            chain = attr_chain(node.func)
            if chain and chain[-1] in COLLECTIVE_NAMES:
                out.append((node, chain[-1]))
                continue
            for callee in graph.resolve_callable(node.func, fn, fn.module):
                if callee.full in bearing:
                    label = chain[-1] if chain else callee.qualname
                    out.append((node, label))
                    break
        return out

    def _config_gate_field(
        self, test: ast.AST, fn: FunctionInfo
    ) -> Optional[str]:
        """The config field a guard tests, when the test references a
        ``...config...`` attribute chain (``config.resilience.elastic``,
        ``self.resilience.config.coordinate_preemption``) or a local
        assigned from one."""

        def field_of(expr: ast.AST) -> Optional[str]:
            for sub in ast.walk(expr):
                chain = attr_chain(sub) if isinstance(sub, ast.Attribute) else None
                if not chain or len(chain) < 2:
                    continue
                if "config" in chain[:-1] or chain[0].endswith("config"):
                    return chain[-1]
            return None

        hit = field_of(test)
        if hit:
            return hit
        # one hop through a local: `coordinate = <config chain>; if coordinate:`
        names = {
            sub.id
            for sub in ast.walk(test)
            if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load)
        }
        if not names:
            return None
        for node in fn.body_nodes():
            if not isinstance(node, ast.Assign):
                continue
            if not any(
                isinstance(t, ast.Name) and t.id in names for t in node.targets
            ):
                continue
            hit = field_of(node.value)
            if hit:
                return hit
        return None

    def _check_guards(
        self, graph: CallGraph, direct, bearing: Set[str], rank: _RankDependence
    ) -> List[Finding]:
        findings: List[Finding] = []
        seen: Set[str] = set()
        for fn in graph.functions:
            calls = self._collective_calls_in(graph, fn, bearing)
            if not calls:
                continue
            local_ranky = rank.local_rank_names(fn)
            # early-exit guards: statements after `if <rank-dep>: return`
            # in the same body are rank-conditional too
            guarded_after: Dict[int, Tuple[str, ast.AST]] = {}
            for stmt in ast.walk(fn.node):
                bodies = []
                for attr in ("body", "orelse", "finalbody"):
                    sub = getattr(stmt, attr, None)
                    if isinstance(sub, list) and sub and isinstance(
                        sub[0], ast.stmt
                    ):
                        bodies.append(sub)
                for body in bodies:
                    for i, s in enumerate(body):
                        if (
                            isinstance(s, ast.If)
                            and _body_is_terminal(s.body)
                            and not s.orelse
                            and rank.expr_is_rank_dependent(
                                s.test, fn, fn.module, local_ranky
                            )
                        ):
                            for later in body[i + 1:]:
                                for sub in ast.walk(later):
                                    guarded_after[id(sub)] = ("early-exit", s.test)
            for call, label in calls:
                guard: Optional[Tuple[str, ast.AST]] = None
                config_fields: List[str] = []
                for anc in fn.module.ancestors(call):
                    if anc is fn.node:
                        break
                    if not isinstance(anc, (ast.If, ast.IfExp)):
                        continue
                    if rank.expr_is_rank_dependent(
                        anc.test, fn, fn.module, local_ranky
                    ):
                        guard = ("branch", anc.test)
                        break
                    field = self._config_gate_field(anc.test, fn)
                    if field is not None:
                        config_fields.append(field)
                if guard is None and id(call) in guarded_after:
                    guard = guarded_after[id(call)]
                if guard is not None:
                    key = f"{fn.full}:{label}:701"
                    if key in seen:
                        continue
                    seen.add(key)
                    findings.append(
                        Finding(
                            code="GL701",
                            path=fn.module.relpath,
                            line=call.lineno,
                            symbol=fn.qualname,
                            detail=label,
                            message=f"collective `{label}` is reachable only "
                            "under a rank-dependent branch "
                            f"(`{_short(guard[1])}`): ranks outside the "
                            "branch never post it — the ranks inside hang. "
                            "Hoist the collective out of the guard; keep "
                            "only rank-local host work inside",
                        )
                    )
                elif guard is None:
                    for config_field in config_fields:
                        if config_field in RANK_UNIFORM_FIELDS:
                            continue
                        key = f"{fn.full}:{label}:{config_field}:704"
                        if key in seen:
                            continue
                        seen.add(key)
                        findings.append(
                            Finding(
                                code="GL704",
                                path=fn.module.relpath,
                                line=call.lineno,
                                symbol=fn.qualname,
                                detail=f"{config_field}->{label}",
                                message=f"collective `{label}` is gated on "
                                f"config field `{config_field}`, which is not "
                                "registered rank-uniform — a launcher handing "
                                "ranks different values hangs the pod. Add the "
                                "field to RANK_UNIFORM_FIELDS (analysis/"
                                "collectives.py) AND document the contract "
                                "(docs/STATIC_ANALYSIS.md), or derive the gate "
                                "from uniform state",
                            )
                        )
        return findings

    # -- GL702: per-rank loop trip counts ---------------------------------

    def _iter_is_uniform(self, it: ast.AST) -> bool:
        """Conservatively rank-uniform iterables: literals, dotted
        config/attr chains, range()/enumerate()/zip() of uniform things.
        A bare local name is NOT uniform — `pending = <per-rank filter>;
        for p in pending: allgather(...)` is exactly the hang GL702
        exists to catch, so a local must be spelled as its (uniform)
        source to pass."""
        if isinstance(it, (ast.List, ast.Tuple, ast.Constant)):
            return True
        chain = attr_chain(it)
        if chain and len(chain) >= 2:
            return True  # config.train.xs / self.epochs — uniform by contract
        if isinstance(it, ast.Call):
            fchain = attr_chain(it.func)
            if fchain and fchain[-1] in ("range", "enumerate", "zip", "len"):
                return all(self._iter_is_uniform(a) for a in it.args)
        return False

    def _check_loops(self, graph: CallGraph, direct) -> List[Finding]:
        findings: List[Finding] = []
        seen: Set[str] = set()
        for mod, call, scope, name in direct:
            for anc in mod.ancestors(call):
                if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                    break  # loops outside the defining function don't count
                hazard = None
                if isinstance(anc, ast.While):
                    if not (
                        isinstance(anc.test, ast.Constant) and anc.test.value
                    ):
                        hazard = f"while {_short(anc.test)}"
                elif isinstance(anc, ast.For):
                    if not self._iter_is_uniform(anc.iter):
                        hazard = f"for ... in {_short(anc.iter)}"
                if hazard is None:
                    continue
                symbol = scope.qualname if scope else "-"
                key = f"{mod.relpath}:{symbol}:{name}"
                if key in seen:
                    continue
                seen.add(key)
                findings.append(
                    Finding(
                        code="GL702",
                        path=mod.relpath,
                        line=call.lineno,
                        symbol=symbol,
                        detail=name,
                        message=f"collective `{name}` inside `{hazard}`: the "
                        "trip count is not provably rank-uniform, and one "
                        "extra iteration on one rank is one unmatched "
                        "collective (pod hang at loop exit) — drive the "
                        "loop from config/constants, or hoist the "
                        "collective",
                    )
                )
                break
        return findings

    # -- GL703: duplicated barrier-name literals --------------------------

    def _check_barrier_names(self, graph: CallGraph, direct) -> List[Finding]:
        # wrappers: package functions forwarding a parameter into the
        # barrier name (``_commit_barrier(name)``) — their literal call-site
        # args are barrier names too
        wrappers: Set[str] = set()
        for _mod, call, scope, name in direct:
            if name != "sync_global_devices" or scope is None or not call.args:
                continue
            arg_names = {
                sub.id for sub in ast.walk(call.args[0])
                if isinstance(sub, ast.Name)
            }
            if arg_names & set(scope.params):
                wrappers.add(scope.full)
        sites: Dict[str, List[Tuple[SourceModule, ast.Call, Optional[FunctionInfo]]]] = {}

        def record(mod, call, scope):
            if not call.args:
                return
            arg = call.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                sites.setdefault(arg.value, []).append((mod, call, scope))

        for mod, call, scope, name in direct:
            if name == "sync_global_devices":
                record(mod, call, scope)
        for mod in graph.ctx.modules:
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                scope = graph.enclosing_function(mod, node)
                for callee in graph.resolve_callable(node.func, scope, mod):
                    if callee.full in wrappers:
                        record(mod, node, scope)
                        break
        findings: List[Finding] = []
        for name, where in sorted(sites.items()):
            if len(where) < 2:
                continue
            for mod, call, scope in where:
                findings.append(
                    Finding(
                        code="GL703",
                        path=mod.relpath,
                        line=call.lineno,
                        symbol=scope.qualname if scope else "-",
                        detail=name,
                        message=f'barrier name "{name}" is used at '
                        f"{len(where)} call sites: jax pairs barriers by "
                        "name, so interleaved arrivals can pair one rank's "
                        "site with another rank's different site — give "
                        "each site a distinct (or parameterized) name",
                    )
                )
        return findings


def _short(node: ast.AST, limit: int = 50) -> str:
    try:
        text = ast.unparse(node)
    except Exception:  # pragma: no cover
        text = "<expr>"
    return text if len(text) <= limit else text[: limit - 1] + "…"
