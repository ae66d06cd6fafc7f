"""Fast-tier wiring for ``scripts/check_metric_names.py``: every
``stats["..."]`` key in ``trlx_tpu/`` follows the ``namespace/name``
convention (legacy allowlist frozen)."""

import importlib.util
import os

import pytest


def _load_checker():
    spec = importlib.util.spec_from_file_location(
        "check_metric_names",
        os.path.join(
            os.path.dirname(__file__), "..", "scripts", "check_metric_names.py"
        ),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_all_metric_keys_are_namespaced():
    checker = _load_checker()
    violations = checker.find_violations()
    assert violations == [], (
        "stats[...] keys violating the namespace/name convention "
        f"(docs/OBSERVABILITY.md): {violations}"
    )


def test_scanner_sees_the_codebase():
    """Guard against the lint silently matching nothing (a regex typo would
    make the convention check vacuous)."""
    checker = _load_checker()
    keys = checker.scanned_keys()
    assert sum(keys.values()) >= 20, f"suspiciously few stats sites: {keys}"
    # canonical keys the trainer loop writes must be visible to the scanner
    assert "time/step" in keys
    assert "time/train_step" in keys
    # rollout-pipeline keys (docs/PERFORMANCE.md) are namespaced, not
    # allowlisted — the convention covers them like any other metric
    assert "time/rollout_host" in keys
    assert "throughput/rollout_overlap_frac" in keys
    # continuous-batching keys (docs/PERFORMANCE.md): the slot-accounting
    # gauges and the engine's refill/segment counters
    assert "throughput/slot_utilization" in keys
    assert "rollout/padded_decode_frac" in keys
    assert "rollout/refill_prefills" in keys
    assert "rollout/refilled_rows" in keys
    assert "rollout/segments" in keys
    # resilience keys (docs/RESILIENCE.md): the statically visible sites —
    # the on-device guard flag and the registry writes for preemption/goodput
    assert "resilience/update_ok" in keys
    assert "resilience/preemptions" in keys
    assert "resilience/goodput_frac" in keys
    # elastic-restore keys (docs/RESILIENCE.md "Elastic restore"): the
    # reshard timing gauge and the elastic-path counter are literal sites
    assert "resilience/reshard_s" in keys
    assert "resilience/elastic_restores" in keys
    # generation-engine keys (docs/PERFORMANCE.md): block-pool / prefix-cache
    # gauges from EngineStats.metrics and the serial path's KV-memory gauge
    assert "memory/kv_cache_bytes" in keys
    assert "engine/kv_blocks_in_use" in keys
    assert "engine/prefix_hit_rate" in keys
    assert "engine/queue_wait_s" in keys
    # paged-prefill / chunked-prefill keys (docs/PERFORMANCE.md "Pallas
    # kernels" + "Chunked prefill"): the refill gather/scatter byte
    # accounting and the measured decode-stall percentiles
    assert "engine/prefill_kernel_pallas" in keys
    assert "engine/refill_gather_bytes" in keys
    assert "engine/refill_scatter_bytes" in keys
    assert "rollout/decode_stall_p50" in keys
    assert "rollout/decode_stall_p95" in keys
    assert "rollout/decode_stall_max" in keys
    assert "rollout/prefill_chunks" in keys
    # speculative continuous batching (docs/PERFORMANCE.md "Speculative
    # continuous batching"): acceptance and round gauges from
    # EngineStats.metrics — literal stats[...] sites
    assert "engine/spec_acceptance_rate" in keys
    assert "engine/spec_tokens_per_round" in keys
    assert "rollout/spec_rounds" in keys
    # multi-position verify kernel: which compute actually ran — a literal
    # site in engine/core.py
    assert "engine/spec_verify_kernel_pallas" in keys
    # distributed-telemetry keys (docs/OBSERVABILITY.md "Distributed
    # telemetry"): the cluster beat's literal set_gauge sites
    assert "cluster/step_skew_s" in keys
    assert "cluster/straggler_rank" in keys
    assert "cluster/step_time_max_s" in keys
    # flight-recorder + observability self-accounting keys
    assert "flightrec/dumps" in keys
    assert "obs/spans_dropped" in keys
    # async actor/learner keys (docs/ASYNC_RL.md): the collector's
    # collection gauges and the queue/channel/supervisor counters
    assert "async/chunks" in keys
    assert "async/staleness_mean" in keys
    assert "async/actor_restarts" in keys
    assert "async/weight_syncs" in keys
    # collective fleet-transport keys (docs/ASYNC_RL.md "Transports"):
    # dissemination-tree egress/latency, membership, and the beat's
    # fleet gauge — all literal sites in transport.py / distributed.py
    assert "async/dissemination_latency_s" in keys
    assert "async/publish_bytes" in keys
    assert "async/fleet_size" in keys
    assert "async/fleet_joins" in keys
    assert "async/fleet_shrinks" in keys
    assert "cluster/fleet_size" in keys
    # training-dynamics / health keys (docs/OBSERVABILITY.md "Training
    # dynamics"): the literal sites — the engine canary gauges, the NaN-guard
    # counters, and the triage-dump counter (the dist/* sketch keys and the
    # per-detector gauges are parameterized f-string emissions, registered in
    # DIST_KEYS / HEALTH_KEYS instead)
    assert "rollout/gen_len_p50" in keys
    assert "rollout/repetition_frac" in keys
    assert "health/kl_ctl_skips" in keys
    assert "health/triage_dumps" in keys
    assert "health/nonfinite_scores" in keys
    assert "health/nonfinite_kl_chunks" in keys
    # mixture-of-experts routing counters (docs/OBSERVABILITY.md "Per-step
    # keys"): literal sites in trainer/base.py::with_router_aux
    assert "moe/dropped_frac" in keys
    assert "moe/load_max_over_mean" in keys
    # the flash kernels' tile walk at a step's width (docs/OBSERVABILITY.md
    # "Per-step keys"): literal sites in trainer/base.py's learn loop
    assert "learn/attn_visited_frac" in keys
    assert "learn/attn_tile" in keys
    assert "learn/attn_interior_frac" in keys


def test_learn_kernel_gauges_registered_and_visible():
    """The learner's ``*_pallas`` gauges (which form a pass took) are
    registered, namespaced, and literal ``stats[...]`` sites the scanner
    sees."""
    checker = _load_checker()
    assert checker.LEARN_KERNEL_KEYS == {"learn/kda_scan_pallas"}
    keys = checker.scanned_keys()
    for key in checker.LEARN_KERNEL_KEYS:
        assert checker._CONVENTION_RE.match(key) and key.endswith("_pallas") and key in keys, key


def test_engine_keys_registered_and_namespaced():
    """Every canonical engine/* + memory gauge key (docs/PERFORMANCE.md) is
    registered in the checker, follows the namespace/name convention, and
    is visible to the static scanner (they are all literal sites)."""
    checker = _load_checker()
    assert checker.ENGINE_KEYS, "engine key registry is empty"
    for key in checker.ENGINE_KEYS:
        assert checker._CONVENTION_RE.match(key), key
    keys = checker.scanned_keys()
    missing = {k for k in checker.ENGINE_KEYS if k not in keys}
    assert missing == set(), f"engine keys not seen by the scanner: {missing}"


def test_serve_keys_registered_and_namespaced():
    """Every canonical serve/* key (docs/SERVING.md) is registered in the
    checker, follows the namespace/name convention, and is visible to the
    static scanner — they are all literal sites in serve/metrics.py (the
    per-tenant/per-class breakdowns are deliberately off-registry: they
    live under ``detail_metrics()``, not the flat step stats)."""
    checker = _load_checker()
    assert checker.SERVE_KEYS, "serve key registry is empty"
    for key in checker.SERVE_KEYS:
        assert checker._CONVENTION_RE.match(key), key
    keys = checker.scanned_keys()
    missing = {k for k in checker.SERVE_KEYS if k not in keys}
    assert missing == set(), f"serve keys not seen by the scanner: {missing}"
    # the SLO headline gauges and the serving-specific engine extensions
    assert {
        "serve/ttft_p95",
        "serve/tpot_p95",
        "serve/queue_wait_p95",
        "serve/rejected",
        "serve/host_tier_relanded",
        "engine/queue_wait_p95",
        "engine/preempted_rows",
        "engine/host_tier_hit_blocks",
        "engine/host_tier_tokens_saved",
    } <= set(keys)


def test_resilience_keys_registered_and_namespaced():
    """Every canonical resilience/* key (docs/RESILIENCE.md) is registered
    in the checker and follows the namespace/name convention — including
    the retry counters the static scan can't see."""
    checker = _load_checker()
    assert checker.RESILIENCE_KEYS, "resilience key registry is empty"
    for key in checker.RESILIENCE_KEYS:
        assert checker._CONVENTION_RE.match(key), key
    # the guard flag and registry writes must also be visible to the scanner
    keys = checker.scanned_keys()
    visible = {k for k in checker.RESILIENCE_KEYS if k in keys}
    assert {"resilience/update_ok", "resilience/preemptions"} <= visible


def test_cluster_flightrec_obs_keys_registered_and_namespaced():
    """Every canonical cluster/* + flightrec/* + obs/* key
    (docs/OBSERVABILITY.md) is registered in the checker, follows the
    convention, and the literal sites are visible to the scanner."""
    checker = _load_checker()
    keys = checker.scanned_keys()
    for registry_name in ("CLUSTER_KEYS", "FLIGHTREC_KEYS", "OBS_KEYS"):
        registry = getattr(checker, registry_name)
        assert registry, f"{registry_name} is empty"
        for key in registry:
            assert checker._CONVENTION_RE.match(key), key
        missing = {k for k in registry if k not in keys}
        assert missing == set(), (
            f"{registry_name} entries not seen by the scanner: {missing}"
        )


@pytest.mark.parametrize("registry_name", ["ATTRIBUTION_KEYS", "SETUP_KEYS", "SETUP_SPAN_NAMES"])
def test_attribution_and_setup_names_registered_and_namespaced(registry_name):
    """The sink's kinds, the record keys made of them and set-up's gauges
    and spans (docs/OBSERVABILITY.md "What happened beneath a span",
    "Set-up") are registered, follow the convention, live in the three
    namespaces, and the literal sites reach the scanner."""
    checker = _load_checker()
    registry = getattr(checker, registry_name)
    assert registry, f"{registry_name} is empty"
    namespaces = {"setup"} if registry_name.startswith("SETUP") else {"host", "runtime", "time"}
    for key in registry:
        assert checker._CONVENTION_RE.match(key), key
        assert key.split("/")[0] in namespaces, key
    keys = checker.scanned_keys()
    if registry_name == "ATTRIBUTION_KEYS":  # the step record's literal writes
        assert {"time/train_step_dispatch", "time/train_step_wait"} <= set(keys)
    source = open(os.path.join(checker.SCAN_DIR, "observability", "__init__.py")).read()
    source += open(os.path.join(checker.SCAN_DIR, "trainer", "base.py")).read()
    source += open(os.path.join(checker.SCAN_DIR, "trlx.py")).read()
    source += open(os.path.join(checker.SCAN_DIR, "observability", "tracing.py")).read()
    unwritten = {k for k in registry
                 if f'"{k}"' not in source and k not in ("host/slow_cycles", "host/slow_steps")}
    assert unwritten == set(), f"{registry_name} entries the program never writes: {unwritten}"


def test_dist_and_health_keys_registered_and_namespaced():
    """Every canonical dist/* sketch key and health/* detector key
    (docs/OBSERVABILITY.md "Training dynamics") is registered in the checker
    and follows the namespace/name convention — including the histogram and
    per-detector keys the static scan can't see (parameterized f-string
    emissions in observability/dynamics.py and health.py)."""
    checker = _load_checker()
    keys = checker.scanned_keys()
    for registry_name in ("DIST_KEYS", "HEALTH_KEYS"):
        registry = getattr(checker, registry_name)
        assert registry, f"{registry_name} is empty"
        for key in registry:
            assert checker._CONVENTION_RE.match(key), key
    # the statically-visible health sites must reach the scanner
    visible = {k for k in checker.HEALTH_KEYS if k in keys}
    assert {
        "health/kl_ctl_skips",
        "health/triage_dumps",
        "rollout/gen_len_p50",
        "rollout/repetition_frac",
    } <= visible


def test_lint_catches_a_bad_key(tmp_path):
    checker = _load_checker()
    bad = tmp_path / "mod.py"
    bad.write_text('stats["no_namespace_key"] = 1.0\nstats["ok/key"] = 2.0\n')
    violations = checker.find_violations(str(tmp_path))
    assert [(v[2]) for v in violations] == ["no_namespace_key"]
