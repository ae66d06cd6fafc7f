"""Convention passes: metric-name namespace (GL501), span-name namespace
(GL502), and config-key resolution (GL601).

``metric-names`` is the framework home of the former standalone
``scripts/check_metric_names.py`` (that script is now a thin shim over
this module — same public helpers, same semantics): every literal
string-keyed ``stats[...]`` subscript and ``metrics.inc/set_gauge(...)``
call site must use a ``namespace/name`` key. ``LEGACY_KEYS`` is frozen;
``RESILIENCE_KEYS`` registers the canonical resilience counters the
static scan can't see (parameterized helper emissions).

``span-names`` (GL502) holds span and complete-event names to the SAME
``namespace/name`` rule: spans land in the same dashboards and merged
multi-rank traces as metrics, so one naming convention covers both.
``LEGACY_SPAN_NAMES`` freezes the five pre-convention trainer spans
(``rollout``/``generate``/``score``/``reward``/``train_step``) — do not
add to it; new spans must be namespaced. AST-based (unlike the GL501 line
scan) so multi-line calls and docstring examples are handled correctly;
dynamically-named spans (f-strings, variables) are out of scope.

``config-keys`` resolves ``config.<section>.<field>`` attribute chains
against the dataclasses in ``data/configs.py`` (sections) and every
``MethodConfig`` subclass in the package (the ``method`` section's field
union). A typo'd knob (``config.train.rollout_pipeline_dept``) otherwise
reads nothing and silently trains with the default.
"""

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from trlx_tpu.analysis.callgraph import attr_chain
from trlx_tpu.analysis.core import (
    AnalysisContext,
    Finding,
    LintPass,
    register_pass,
)

# ---------------------------------------------------------------------------
# metric names (the former scripts/check_metric_names.py, verbatim rules)
# ---------------------------------------------------------------------------

# \bstats\[ : the dict must be *named* stats (not spec_stats, device_stats…)
# Second alternative: MetricsRegistry writes — receivers named/suffixed
# "metrics" calling inc()/set_gauge() with a literal first argument (the
# registry's observe() is excluded: RecompileWatchdog.observe's first arg is
# a program name, not a metric key).
_KEY_RE = re.compile(
    r'\bstats\[\s*f?"([^"]+)"'
    r'|\bmetrics\.(?:inc|set_gauge)\(\s*f?"([^"]+)"'
)

# namespace/name: lowercase_snake namespace, then anything non-empty (names
# may carry f-string fields, sweep suffixes, dots, @-qualifiers)
_CONVENTION_RE = re.compile(r"^[a-z][a-z0-9_]*/\S+$")

# Pre-convention keys, kept for dashboard/log continuity. Do not add to this
# list — new metrics must be namespaced.
LEGACY_KEYS = frozenset({
    "learning_rate",
    "kl_ctl_value",
})

# Canonical resilience/* metric keys (docs/RESILIENCE.md). The retry
# counters are emitted through a parameterized helper
# (HostCallGuard._inc(f"resilience/{name}_retries")) the static scan can't
# see, so the full set is registered here; tests/test_metric_names.py
# asserts every entry follows the convention and that the statically
# visible ones reach the scanner.
RESILIENCE_KEYS = frozenset({
    "resilience/update_ok",
    "resilience/nonfinite_updates",
    "resilience/skipped_updates",
    "resilience/rollbacks",
    "resilience/goodput_frac",
    "resilience/preemptions",
    "resilience/reward_retries",
    "resilience/reward_failures",
    "resilience/reward_fallbacks",
    "resilience/publish_retries",
    "resilience/publish_failures",
    "resilience/publish_fallbacks",
    # elastic topology-change restore (docs/RESILIENCE.md "Elastic
    # restore"): wall-seconds of the host-side reshard, and how many
    # restores took the elastic path this run
    "resilience/reshard_s",
    "resilience/elastic_restores",
})

# Canonical generation-engine metric keys (trlx_tpu/engine/,
# docs/PERFORMANCE.md): the paged-KV block-pool and prefix-cache gauges,
# plus the KV-memory gauge both backends (and the serial sampler) report.
# All are statically visible stats[...] / set_gauge sites, but the registry
# is the single list tests assert convention + visibility against —
# tests/test_metric_names.py.
ENGINE_KEYS = frozenset({
    "engine/kv_blocks_in_use",
    "engine/block_pool_occupancy",
    "engine/prefix_hit_rate",
    "engine/prefix_tokens_saved",
    "engine/queue_wait_s",
    "memory/kv_cache_bytes",
    # paged decode/prefill compute path gauges (0/1): engine.decode_kernel
    # / engine.prefill_kernel — the in-place Pallas kernels
    # (ops/paged_attention.py, ops/paged_prefill.py) vs the gather/scatter
    # references (docs/PERFORMANCE.md "Pallas kernels")
    "engine/decode_kernel_pallas",
    "engine/prefill_kernel_pallas",
    # analytic bytes the refill prefills move through transient dense
    # views (pool→view gather on entry, span→pool scatter on exit):
    # exactly 0 under the in-place prefill kernel
    "engine/refill_gather_bytes",
    "engine/refill_scatter_bytes",
    # chunked-prefill scheduling (engine.prefill_chunk,
    # docs/PERFORMANCE.md "Chunked prefill"): mid-chunk program calls, and
    # the measured wall-seconds live decode slots spent waiting on prefill
    # work — one sample per stalling prefill event
    "rollout/prefill_chunks",
    "rollout/decode_stall_p50",
    "rollout/decode_stall_p95",
    "rollout/decode_stall_max",
    # speculative continuous batching (engine.speculative,
    # docs/PERFORMANCE.md "Speculative continuous batching"): fraction of
    # draft proposals the target accepted, committed tokens per live
    # row-round (the throughput multiplier, ∈ [1, gamma+1]), and
    # draft-propose/verify rounds run this collection
    "engine/spec_acceptance_rate",
    "engine/spec_tokens_per_round",
    "rollout/spec_rounds",
    # spec verify compute path gauge (0/1): the in-place multi-position
    # verify kernel (ops/paged_attention.py::paged_verify_attention, runs
    # when engine.decode_kernel: pallas composes with engine.speculative)
    # vs the gather → shared round → scatter reference
    "engine/spec_verify_kernel_pallas",
    # serving extensions on the engine (docs/SERVING.md): per-request
    # queue-wait percentiles from the enqueue→prefill spans, priority-
    # preemption count, and the host-tier re-land accounting (blocks
    # written back device-side instead of re-prefilled, and the prefill
    # tokens that saved)
    "engine/queue_wait_p50",
    "engine/queue_wait_p95",
    "engine/preempted_rows",
    "engine/host_tier_hit_blocks",
    "engine/host_tier_tokens_saved",
})

# Which form a learner's pass took (0/1), by the ``*_pallas`` convention
# (analysis/kernels.py GL1002): the chunked delta rule of a model with
# ``KDAMixer`` layers, stamped in trainer/base.py::with_router_aux from the
# function that makes the choice (ops/delta_rule.py::scan_takes_kernel: the
# Pallas kernel at a head size of whole lanes, else the jax.numpy form)
LEARN_KERNEL_KEYS = frozenset({
    "learn/kda_scan_pallas",
})

# Canonical serving-frontend keys (trlx_tpu/serve/, docs/SERVING.md): the
# FLAT aggregate gauges ServeMetrics.metrics() emits into the training
# metric stream — TTFT/TPOT/queue-wait percentiles over all serve traffic,
# admission counters (SLO 429s, drain 503s, flood-drill sheds), terminal
# counts, and the host-tier occupancy counters. Per-tenant/per-class
# breakdowns deliberately stay OFF this registry (unbounded cardinality)
# and live on the HTTP /metrics endpoint instead. All literal stats[...]
# sites in serve/metrics.py.
SERVE_KEYS = frozenset({
    "serve/ttft_p50",
    "serve/ttft_p95",
    "serve/tpot_p50",
    "serve/tpot_p95",
    "serve/queue_wait_p50",
    "serve/queue_wait_p95",
    "serve/admitted",
    "serve/rejected",
    "serve/drain_rejected",
    "serve/flood_rejected",
    "serve/completed",
    "serve/failed",
    "serve/dropped",
    "serve/active",
    "serve/streamed_tokens",
    "serve/host_tier_blocks",
    "serve/host_tier_spilled",
    "serve/host_tier_relanded",
    "serve/params_version",
})

# Canonical cross-rank telemetry gauges (observability/distributed.py,
# docs/OBSERVABILITY.md "Distributed telemetry"): published every step
# boundary from the packed allgather matrix — min/mean/max/skew of the
# per-rank scalars plus the straggler verdict. All literal set_gauge sites.
CLUSTER_KEYS = frozenset({
    "cluster/size",
    "cluster/step_time_min_s",
    "cluster/step_time_mean_s",
    "cluster/step_time_max_s",
    "cluster/step_skew_s",
    "cluster/host_wait_mean_s",
    "cluster/host_wait_max_s",
    "cluster/tokens_per_sec_min",
    "cluster/tokens_per_sec_sum",
    "cluster/device_bytes_in_use_max",
    "cluster/straggler_rank",
    "cluster/fleet_size",
})

# Canonical async actor/learner keys (trlx_tpu/async_rl/, docs/ASYNC_RL.md):
# the learner-side collection gauges (queue depth, staleness at consumption,
# actor idle fraction) plus the counters the queue/channel/supervisor emit.
# async/staleness is additionally observed as a histogram, so the tracker
# stream carries async/staleness_mean|_max|_count summaries per window.
ASYNC_KEYS = frozenset({
    "async/chunks",
    "async/queue_depth",
    "async/staleness_mean",
    "async/staleness_max",
    "async/learner_wait_s",
    "async/actor_idle_frac",
    "async/dropped_chunks",
    "async/requeued_chunks",
    "async/actor_restarts",
    "async/weight_syncs",
    "async/weight_sync_drops",
    # collective fleet transport (async_rl/transport.py, docs/ASYNC_RL.md
    # "Transports"): dissemination-tree publish egress + ack latency,
    # live membership, and elastic join/shrink counters
    "async/dissemination_latency_s",
    "async/publish_bytes",
    "async/fleet_size",
    "async/fleet_joins",
    "async/fleet_shrinks",
})

# Canonical async span names (GL502-namespaced; the actor's per-chunk span
# lands on its own thread track in the merged trace).
ASYNC_SPAN_NAMES = frozenset({
    "async/actor_chunk",
})

# Crash flight recorder accounting (observability/flightrec.py,
# docs/OBSERVABILITY.md "Flight recorder").
FLIGHTREC_KEYS = frozenset({
    "flightrec/dumps",
    "flightrec/records",
})

# Observability self-accounting (docs/OBSERVABILITY.md): the span tracer's
# silent drop counter surfaced as a gauge.
OBS_KEYS = frozenset({
    "obs/spans_dropped",
})

# What the host and the runtime did in a record's interval, and the
# intervals that ran long (observability/tracing.py's sink,
# trainer/base.py::attributed_between, docs/OBSERVABILITY.md "What happened
# beneath a span"). The record keys are built by one function from the
# sink's marks and the counters through an f-string, so the registry is
# their canonical list; the runtime/* and host/gc entries without a record
# are the sink's kinds (span-event names and keys of tracing.mark()).
ATTRIBUTION_KEYS = frozenset({
    "time/generate_dispatch",
    "time/generate_wait",
    "time/train_step_dispatch",
    "time/train_step_wait",
    "host/gc_pause_s",
    "host/gc_gen2",
    "host/cpu_s",
    "host/invol_switches",
    "host/major_faults",
    "host/proc_cpu_s",
    "host/proc_invol_switches",
    "host/slow_cycles",
    "host/slow_steps",
    "host/gc",
    "runtime/retrace_s",
    "runtime/compile_s",
    "runtime/trace",
    "runtime/lower",
    "runtime/compile",
    "runtime/cache_load",
    "runtime/programs",
    "runtime/cache_hits",
    "runtime/cache_misses",
})

# Set-up's account, frozen as gauges when the second collection begins
# (observability/__init__.py::Observability.freeze_setup, set in one loop),
# and the spans it is read from.
SETUP_KEYS = frozenset({
    "setup/import_s",
    "setup/build_s",
    "setup/init_model_s",
    "setup/first_eval_s",
    "setup/first_cycle_s",
    "setup/trace_lower_s",
    "setup/compile_s",
    "setup/cache_load_s",
    "setup/compile_load_s",
    "setup/gc_pause_s",
    "setup/programs",
    "setup/cache_hits",
    "setup/cache_misses",
    "setup/total_s",
})
SETUP_SPAN_NAMES = frozenset({
    "setup/runtime_init",
    "setup/build_trainer",
    "setup/init_model",
    "setup/tokenizer",
    "setup/pipelines",
    "setup/first_eval",
})

# Canonical training-dynamics sketch keys (observability/dynamics.py,
# docs/OBSERVABILITY.md "Training dynamics"). The ``*_hist`` keys carry the
# on-device fixed-bin histogram counts through the stats fetch; the host
# summarizer collapses each into ``_p05/_p50/_p95`` percentile gauges (the
# summary keys are emitted through parameterized f-strings, so the registry
# is their single canonical list).
DIST_KEYS = frozenset({
    "dist/log_ratio_hist",
    "dist/kl_hist",
    "dist/ref_kl_hist",
    "dist/advantages_hist",
    "dist/value_error_hist",
    "dist/entropy_hist",
    "dist/reward_margin_hist",
    # host-side summaries (DynamicsSummarizer): one triple per histogram
    "dist/log_ratio_p05", "dist/log_ratio_p50", "dist/log_ratio_p95",
    "dist/kl_p05", "dist/kl_p50", "dist/kl_p95",
    "dist/ref_kl_p05", "dist/ref_kl_p50", "dist/ref_kl_p95",
    "dist/advantages_p05", "dist/advantages_p50", "dist/advantages_p95",
    "dist/value_error_p05", "dist/value_error_p50", "dist/value_error_p95",
    "dist/entropy_p05", "dist/entropy_p50", "dist/entropy_p95",
    "dist/reward_margin_p05", "dist/reward_margin_p50",
    "dist/reward_margin_p95",
    # mass of per-token ratio beyond the PPO clip window [1−ε, 1+ε]
    "dist/ratio_outside_clip_frac",
})

# Canonical RL health keys (observability/health.py, docs/OBSERVABILITY.md
# "Training dynamics"): one 0/1 gauge per windowed detector plus the overall
# verdict (detector gauges are published through a parameterized f-string —
# registered here), the rollout canary gauges, and the counters the NaN
# guards bump (kl-controller skips, sanitized scores/KL chunks, triage
# artifact dumps).
HEALTH_KEYS = frozenset({
    "health/kl_runaway",
    "health/entropy_collapse",
    "health/clipfrac_saturation",
    "health/value_ev_collapse",
    "health/reward_flatline",
    "health/gen_canary",
    "health/verdict",
    "health/kl_ctl_skips",
    "health/triage_dumps",
    "health/nonfinite_scores",
    "health/nonfinite_kl_chunks",
    # rollout-side generation canary (engine harvest + finalize host twin)
    "rollout/gen_len_p50",
    "rollout/gen_len_p95",
    "rollout/repetition_frac",
})


def _iter_line_keys(lines) -> "List[Tuple[int, str]]":
    """(lineno, key) for every literal metric-key site in ``lines`` — the
    single scanning loop behind the shim helpers and the GL501 pass."""
    out: List[Tuple[int, str]] = []
    for lineno, line in enumerate(lines, start=1):
        for groups in _KEY_RE.findall(line):
            out.append((lineno, groups[0] or groups[1]))
    return out


def _iter_dir_keys(scan_dir: str):
    """(relpath, lineno, key) over every .py under ``scan_dir``; relpaths
    relative to the scan dir's parent (the shim's historical repo-root-
    relative output)."""
    base = os.path.dirname(os.path.abspath(scan_dir))
    for dirpath, _dirnames, filenames in os.walk(scan_dir):
        if "__pycache__" in dirpath:
            continue
        for filename in sorted(filenames):
            if not filename.endswith(".py"):
                continue
            path = os.path.join(dirpath, filename)
            with open(path) as f:
                for lineno, key in _iter_line_keys(f):
                    yield os.path.relpath(path, base), lineno, key


def _breaks_convention(key: str) -> bool:
    return key not in LEGACY_KEYS and not _CONVENTION_RE.match(key)


def find_violations(scan_dir: str) -> List[Tuple[str, int, str]]:
    """All (relpath, lineno, key) whose key breaks the convention."""
    return [
        (relpath, lineno, key)
        for relpath, lineno, key in _iter_dir_keys(scan_dir)
        if _breaks_convention(key)
    ]


def scanned_keys(scan_dir: str) -> Dict[str, int]:
    """key → occurrence count over the tree (for the test's sanity check
    that the scanner actually sees the codebase's stats writes)."""
    counts: Dict[str, int] = {}
    for _relpath, _lineno, key in _iter_dir_keys(scan_dir):
        counts[key] = counts.get(key, 0) + 1
    return counts


@register_pass
class MetricNamesPass(LintPass):
    name = "metric-names"
    codes = ("GL501",)
    description = "metric keys must follow the namespace/name convention"

    def run(self, ctx: AnalysisContext) -> List[Finding]:
        findings: List[Finding] = []
        for mod in ctx.modules:
            for lineno, key in _iter_line_keys(mod.lines):
                if not _breaks_convention(key):
                    continue
                findings.append(
                    Finding(
                        code="GL501",
                        path=mod.relpath,
                        line=lineno,
                        symbol="-",
                        detail=key,
                        message=f'metric key "{key}" violates the '
                        "namespace/name convention "
                        "(docs/OBSERVABILITY.md; LEGACY_KEYS is frozen)",
                    )
                )
        return findings


# ---------------------------------------------------------------------------
# span names
# ---------------------------------------------------------------------------

# call names whose first literal-string argument is a span/track name:
# Tracer.span / Observability.span / module-level span(),
# Tracer.add_complete_event, and the engine's injected `self._span` seam
_SPAN_FUNCS = frozenset({"span", "_span", "add_complete_event"})

# Pre-convention trainer span names, kept for trace/dashboard continuity
# (they predate the namespace rule and appear in every committed trace).
# FROZEN — new spans must be namespaced.
LEGACY_SPAN_NAMES = frozenset({
    "rollout",
    "generate",
    "score",
    "reward",
    "train_step",
})


def _span_name_violation(name: str) -> bool:
    return name not in LEGACY_SPAN_NAMES and not _CONVENTION_RE.match(name)


@register_pass
class SpanNamesPass(LintPass):
    name = "span-names"
    codes = ("GL502",)
    description = "span names must follow the namespace/name convention"

    def run(self, ctx: AnalysisContext) -> List[Finding]:
        findings: List[Finding] = []
        graph = ctx.callgraph
        for mod in ctx.modules:
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call) or not node.args:
                    continue
                func = node.func
                if isinstance(func, ast.Attribute):
                    fname = func.attr
                elif isinstance(func, ast.Name):
                    fname = func.id
                else:
                    continue
                if fname not in _SPAN_FUNCS:
                    continue
                arg = node.args[0]
                if not (isinstance(arg, ast.Constant) and isinstance(arg.value, str)):
                    continue  # dynamic names are out of static scope
                name = arg.value
                if not _span_name_violation(name):
                    continue
                scope = graph.enclosing_function(mod, node)
                findings.append(
                    Finding(
                        code="GL502",
                        path=mod.relpath,
                        line=node.lineno,
                        symbol=scope.qualname if scope else "-",
                        detail=name,
                        message=f'span name "{name}" violates the '
                        "namespace/name convention (docs/OBSERVABILITY.md; "
                        "LEGACY_SPAN_NAMES is frozen)",
                    )
                )
        return findings


# ---------------------------------------------------------------------------
# config keys
# ---------------------------------------------------------------------------

# receivers we trust to be a TRLConfig: `config.train.x`, `self.config.train.x`
_CONFIG_RECEIVERS = {"config", "cfg", "baseconfig"}


def _dataclass_members(node: ast.ClassDef) -> Set[str]:
    out: Set[str] = set()
    for stmt in node.body:
        if isinstance(stmt, ast.AnnAssign) and isinstance(stmt.target, ast.Name):
            out.add(stmt.target.id)
        elif isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
        elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.add(stmt.name)
    return out


@register_pass
class ConfigKeysPass(LintPass):
    name = "config-keys"
    codes = ("GL601",)
    description = "config.<section>.<field> must resolve to a declared field"

    def run(self, ctx: AnalysisContext) -> List[Finding]:
        sections = self._collect_sections(ctx)
        if not sections:
            return []
        graph = ctx.callgraph
        findings: List[Finding] = []
        for mod in ctx.modules:
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Attribute):
                    continue
                chain = attr_chain(node)
                if not chain or len(chain) < 3:
                    continue
                hit = self._match_section(chain, sections)
                if hit is None:
                    continue
                section, fieldname = hit
                if fieldname in sections[section]:
                    continue
                scope = graph.enclosing_function(mod, node)
                symbol = scope.qualname if scope else "-"
                findings.append(
                    Finding(
                        code="GL601",
                        path=mod.relpath,
                        line=node.lineno,
                        symbol=symbol,
                        detail=f"{section}.{fieldname}",
                        message=f"`config.{section}.{fieldname}` does not "
                        f"resolve to a declared field of the `{section}` "
                        "config dataclass (data/configs.py) — typo'd knobs "
                        "silently read defaults",
                    )
                )
        # one finding per (file, detail): repeated uses of the same bad key
        # in one file are one decision
        seen: Set[str] = set()
        unique: List[Finding] = []
        for f in sorted(findings, key=lambda f: (f.path, f.line)):
            k = f"{f.path}:{f.detail}"
            if k not in seen:
                seen.add(k)
                unique.append(f)
        return unique

    def _collect_sections(self, ctx: AnalysisContext) -> Dict[str, Set[str]]:
        """section name → allowed member names. Sections come from
        TRLConfig's fields; `method` is the union over MethodConfig and
        every class in the package inheriting (transitively, by name) from
        it."""
        classes: Dict[str, ast.ClassDef] = {}
        bases: Dict[str, List[str]] = {}
        trl: Optional[ast.ClassDef] = None
        for mod in ctx.modules:
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.ClassDef):
                    classes[node.name] = node
                    bases[node.name] = [
                        ".".join(attr_chain(b) or ["?"]) for b in node.bases
                    ]
                    if node.name == "TRLConfig":
                        trl = node
        if trl is None:
            return {}

        def inherits_method_config(name: str, seen: Set[str]) -> bool:
            if name == "MethodConfig":
                return True
            if name in seen:
                return False
            seen.add(name)
            return any(
                inherits_method_config(b.rsplit(".", 1)[-1], seen)
                for b in bases.get(name, [])
            )

        method_members: Set[str] = set()
        for name, node in classes.items():
            if inherits_method_config(name, set()):
                method_members |= _dataclass_members(node)

        sections: Dict[str, Set[str]] = {}
        for stmt in trl.body:
            if not (
                isinstance(stmt, ast.AnnAssign)
                and isinstance(stmt.target, ast.Name)
            ):
                continue
            section = stmt.target.id
            ann = stmt.annotation
            ann_name = (attr_chain(ann) or ["?"])[-1]
            if ann_name == "MethodConfig" or section == "method":
                sections[section] = set(method_members)
            elif ann_name in classes:
                sections[section] = _dataclass_members(classes[ann_name])
        return sections

    def _match_section(
        self, chain: List[str], sections: Dict[str, Set[str]]
    ) -> Optional[Tuple[str, str]]:
        """Match ``[..., <config-receiver>, <section>, <field>, ...]``."""
        for i in range(len(chain) - 2):
            recv, section, fieldname = chain[i], chain[i + 1], chain[i + 2]
            if section not in sections:
                continue
            if recv in _CONFIG_RECEIVERS or recv.endswith("config"):
                return section, fieldname
        return None
