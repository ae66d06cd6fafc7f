"""Training-dynamics observability (docs/OBSERVABILITY.md "Training
dynamics"): on-device distribution sketches, windowed RL health detectors,
and automatic bad-batch triage.

Covers the acceptance criteria end to end:
- sketch emission is bit-identical in loss/grads and adds no recompiles;
- each detector trips on a synthetic sick stream and stays quiet on a
  healthy one;
- the ``health_trip@step:N`` fault exercises detector → flightrec dump →
  ``triage/step<N>.npz`` deterministically, and the artifact round-trips;
- a guard-rejected (NaN) update triages the offending batch too.
"""

import json

import numpy as np
import pytest

from trlx_tpu.observability.dynamics import (
    SKETCH_BINS,
    SKETCH_RANGES,
    DynamicsSummarizer,
    hist_mass_outside,
    hist_percentile,
    sketch,
    sketch_np,
)
from trlx_tpu.observability.health import (
    DETECTORS,
    REWARD_FLATLINE_WINDOW,
    HealthMonitor,
)


# ---------------------------------------------------------------------------
# sketches
# ---------------------------------------------------------------------------


def test_sketch_matches_numpy_twin_and_respects_mask():
    rng = np.random.default_rng(0)
    x = rng.normal(0.0, 0.6, size=(4, 16)).astype(np.float32)
    mask = (rng.random((4, 16)) > 0.3).astype(np.float32)
    lo, hi = SKETCH_RANGES["log_ratio"]

    device = np.asarray(sketch(x, mask, lo=lo, hi=hi))
    host = sketch_np(x, mask, lo=lo, hi=hi)
    np.testing.assert_allclose(device, host, rtol=0, atol=0)
    # total mass is exactly the masked token count; masked-out tokens gone
    assert device.sum() == mask.sum()
    assert device.shape == (SKETCH_BINS,)


def test_sketch_clamps_tails_into_edge_bins():
    lo, hi = SKETCH_RANGES["log_ratio"]
    counts = sketch_np(np.array([-100.0, 100.0, 0.0]), None, lo=lo, hi=hi)
    assert counts[0] == 1.0  # below-range mass in the first bin
    assert counts[-1] == 1.0  # above-range mass in the last bin
    assert counts.sum() == 3.0


def test_hist_percentile_tracks_numpy_percentile():
    rng = np.random.default_rng(1)
    x = rng.normal(0.0, 0.25, size=20_000).astype(np.float32)
    lo, hi = -1.0, 1.0
    counts = sketch_np(x, None, lo=lo, hi=hi)
    width = (hi - lo) / SKETCH_BINS
    for q in (5.0, 50.0, 95.0):
        est = hist_percentile(counts, lo, hi, q)
        true = float(np.percentile(x, q))
        assert abs(est - true) <= width, (q, est, true)


def test_hist_mass_outside_interpolates():
    # uniform mass over [-1, 1): outside [-0.5, 0.5] is exactly half
    counts = np.ones(SKETCH_BINS)
    frac = hist_mass_outside(counts, -1.0, 1.0, -0.5, 0.5)
    assert abs(frac - 0.5) < 1e-9
    assert hist_mass_outside(np.zeros(SKETCH_BINS), -1.0, 1.0, -0.5, 0.5) == 0.0


def test_summarizer_emits_percentiles_and_clip_frac():
    rng = np.random.default_rng(2)
    lo, hi = SKETCH_RANGES["log_ratio"]
    counts = sketch_np(
        rng.normal(0.0, 0.4, size=5000).astype(np.float32), None, lo=lo, hi=hi
    )
    summarizer = DynamicsSummarizer(cliprange=0.2)
    out = summarizer.summarize(
        {
            "dist/log_ratio_hist": counts,
            "dist/entropy_hist": np.zeros(SKETCH_BINS),  # empty mask: skipped
            "losses/total_loss": 1.0,  # scalar: ignored
        }
    )
    for suffix in ("p05", "p50", "p95"):
        assert f"dist/log_ratio_{suffix}" in out
    assert out["dist/log_ratio_p05"] < out["dist/log_ratio_p50"] < out["dist/log_ratio_p95"]
    assert 0.0 < out["dist/ratio_outside_clip_frac"] < 1.0
    assert not any(k.startswith("dist/entropy") for k in out)


# ---------------------------------------------------------------------------
# detectors (synthetic metric streams)
# ---------------------------------------------------------------------------


def _monitor(**kwargs):
    kwargs.setdefault("window", 2)
    return HealthMonitor(metrics=None, flightrec=None, **kwargs)


def test_healthy_stream_stays_ok():
    mon = _monitor()
    mon.observe_rollout(
        {
            "policy/sqrt_kl": 0.05,
            "exp_scores/mean": 1.0,
            "rollout/repetition_frac": 0.1,
        }
    )
    for step in range(6):
        gauges = mon.update(
            {
                "dist/entropy_p50": 3.0,
                "policy/clipfrac": 0.1,
                "values/values_error": 0.2,
                "returns/std": 1.0,
            },
            step=step,
        )
    assert mon.verdict == "ok"
    assert gauges["health/verdict"] == 0.0
    assert all(gauges[f"health/{name}"] == 0.0 for name in DETECTORS)


def test_entropy_collapse_trips_once_window_full():
    mon = _monitor()
    assert mon.update({"dist/entropy_p50": 0.01}, step=0)["health/entropy_collapse"] == 0.0
    gauges = mon.update({"dist/entropy_p50": 0.01}, step=1)
    assert gauges["health/entropy_collapse"] == 1.0
    assert mon.verdict == "entropy_collapse"
    assert mon.just_tripped == "entropy_collapse"
    # a sustained trip is not a new transition
    mon.update({"dist/entropy_p50": 0.01}, step=2)
    assert mon.just_tripped is None
    assert mon.trip_counts["entropy_collapse"] == 1


def test_a_detector_that_flaps_cues_one_dump(trlx_log_records):
    """An untrained critic's explained variance hovers around 0: the
    windowed mean crosses the floor again and again. Every crossing is
    counted and shows on the gauge; only the first cues the flight-record and
    triage dump (``just_tripped``), whose un-jitted forward stalls the loop."""
    mon = _monitor()
    bad = {"values/values_error": 1.2, "returns/std": 1.0}   # EV -0.2
    good = {"values/values_error": 0.7, "returns/std": 1.0}  # EV +0.3
    cues, gauge = [], []
    for step, stats in enumerate([bad, bad, good, good, bad, bad, good, good, bad, bad]):
        gauge.append(mon.update(stats, step=step)["health/value_ev_collapse"])
        cues.append(mon.just_tripped)
    assert gauge == [0.0, 1.0, 0.0, 0.0, 0.0, 1.0, 0.0, 0.0, 0.0, 1.0]
    assert mon.trip_counts["value_ev_collapse"] == 3
    assert cues == [None, "value_ev_collapse"] + [None] * 8
    assert sum("value_ev_collapse tripped" in r.getMessage() for r in trlx_log_records) == 1
    # another detector's first trip still cues its own dump
    mon.update({"dist/entropy_p50": 0.01}, step=10)
    mon.update({"dist/entropy_p50": 0.01}, step=11)
    assert mon.just_tripped == "entropy_collapse"


def test_kl_runaway_vs_controller_target():
    mon = _monitor(kl_target=0.1)
    for _ in range(2):
        mon.observe_rollout({"policy/sqrt_kl": 1.0})  # KL = 1.0 >> 4 × 0.1
    assert mon.update({}, step=0)["health/kl_runaway"] == 1.0
    assert mon.verdict == "kl_runaway"
    # without a target the detector is disabled
    mon2 = _monitor(kl_target=None)
    for _ in range(2):
        mon2.observe_rollout({"policy/sqrt_kl": 1.0})
    assert mon2.update({}, step=0)["health/kl_runaway"] == 0.0


def test_clipfrac_saturation_and_value_ev_collapse():
    mon = _monitor()
    for step in range(2):
        gauges = mon.update(
            {
                "policy/clipfrac": 0.95,
                "values/values_error": 10.0,
                "returns/std": 1.0,  # EV = 1 − 10/1 = −9
            },
            step=step,
        )
    assert gauges["health/clipfrac_saturation"] == 1.0
    assert gauges["health/value_ev_collapse"] == 1.0
    # clipfrac_saturation comes first in DETECTORS order → names the verdict
    assert mon.verdict == "clipfrac_saturation"


def test_reward_flatline_and_gen_canary():
    mon = _monitor()
    for _ in range(REWARD_FLATLINE_WINDOW):
        mon.observe_rollout(
            {"exp_scores/mean": 2.5, "rollout/repetition_frac": 0.95}
        )
    gauges = mon.update({}, step=0)
    assert gauges["health/reward_flatline"] == 1.0
    assert gauges["health/gen_canary"] == 1.0


def test_nonfinite_signals_are_ignored():
    mon = _monitor()
    mon.observe_rollout({"policy/sqrt_kl": float("nan")})
    for step in range(4):
        gauges = mon.update(
            {"dist/entropy_p50": float("nan"), "policy/clipfrac": float("inf")},
            step=step,
        )
    assert mon.verdict == "ok"
    assert all(v == 0.0 for v in gauges.values())


def test_force_trip_is_consumed_by_one_update():
    mon = _monitor()
    mon.force_trip("fault_plan", step=3)
    gauges = mon.update({}, step=3)
    assert gauges["health/verdict"] == 1.0
    assert mon.verdict == "injected:fault_plan"
    assert mon.just_tripped == "injected:fault_plan"
    # the injection does not persist past its step
    mon.update({}, step=4)
    assert mon.verdict == "ok"
    assert mon.just_tripped is None


def test_kl_controller_skips_nonfinite_updates():
    from trlx_tpu.models.ppo import AdaptiveKLController

    ctl = AdaptiveKLController(init_kl_coef=0.05, target=6.0, horizon=10_000)
    before = ctl.value
    ctl.update(float("nan"), n_steps=8)
    assert ctl.value == before and np.isfinite(ctl.value)
    assert ctl.skipped == 1
    ctl.update(12.0, n_steps=8)  # finite updates still move β
    assert np.isfinite(ctl.value) and ctl.value != before


def test_engine_harvest_canary():
    from trlx_tpu.engine.core import EngineStats

    stats = EngineStats()
    tokens = np.array([[7, 7, 7, 7], [1, 2, 3, 0]])
    mask = np.array([[1, 1, 1, 1], [1, 1, 1, 0]], np.float32)
    stats.note_harvest(tokens, mask)
    # row 0: 3 repeated pairs of 3; row 1: 0 of 2 → 3/5
    assert stats.repetition_frac == pytest.approx(3.0 / 5.0)
    gauges = stats.metrics()
    assert gauges["rollout/gen_len_p50"] == pytest.approx(3.5)
    assert gauges["rollout/repetition_frac"] == pytest.approx(3.0 / 5.0)


# ---------------------------------------------------------------------------
# bit-equivalence: sketches perturb nothing
# ---------------------------------------------------------------------------


def test_ppo_loss_bitwise_identical_with_sketches():
    """Enabling sketches must not change a single bit of loss or gradients —
    the sketch reads stop-gradient'd intermediates and feeds nothing back."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.ppo import PPOConfig

    rng = np.random.default_rng(3)
    B, R = 4, 8
    logprobs = jnp.asarray(rng.normal(-1.0, 0.3, (B, R)), jnp.float32)
    values = jnp.asarray(rng.normal(0.0, 0.5, (B, R)), jnp.float32)
    old_logprobs = jnp.asarray(rng.normal(-1.0, 0.3, (B, R)), jnp.float32)
    old_values = jnp.asarray(rng.normal(0.0, 0.5, (B, R)), jnp.float32)
    advantages = jnp.asarray(rng.normal(0.0, 1.0, (B, R)), jnp.float32)
    returns = jnp.asarray(rng.normal(0.0, 1.0, (B, R)), jnp.float32)
    mask = jnp.asarray(rng.random((B, R)) > 0.2, jnp.float32)

    def run(dist_sketches):
        method = PPOConfig(dist_sketches=dist_sketches)

        def objective(lp, v):
            loss, stats = method.loss(
                lp, v, old_logprobs, old_values, advantages, returns, mask
            )
            return loss, stats

        (loss, stats), grads = jax.jit(
            jax.value_and_grad(objective, argnums=(0, 1), has_aux=True)
        )(logprobs, values)
        return np.asarray(loss), [np.asarray(g) for g in grads], stats

    loss_off, grads_off, stats_off = run(False)
    loss_on, grads_on, stats_on = run(True)
    assert loss_on.tobytes() == loss_off.tobytes()
    for g_on, g_off in zip(grads_on, grads_off):
        assert g_on.tobytes() == g_off.tobytes()
    # the sketch pytree rode along only when enabled
    assert "dist/log_ratio_hist" in stats_on
    assert np.asarray(stats_on["dist/log_ratio_hist"]).shape == (SKETCH_BINS,)
    assert not any(k.startswith("dist/") for k in stats_off)


# ---------------------------------------------------------------------------
# end-to-end: stream, fault trigger, triage artifact
# ---------------------------------------------------------------------------


def _health_ppo_config(tmp_path, **train_overrides):
    from trlx_tpu.data.default_configs import default_ppo_config

    train = dict(
        seq_length=24,
        batch_size=8,
        total_steps=2,
        eval_interval=10,
        checkpoint_interval=10,
        epochs=1,
        save_best=False,
        checkpoint_dir=str(tmp_path / "ckpts"),
        logging_dir=str(tmp_path / "logs"),
        tracker="jsonl",
    )
    train.update(train_overrides)
    return default_ppo_config().evolve(
        train=train,
        model=dict(model_path="builtin:gpt2-test", num_layers_unfrozen=1),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        method=dict(
            num_rollouts=8,
            chunk_size=8,
            ppo_epochs=2,
            gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0, do_sample=True),
        ),
    )


def _run_health_ppo(config):
    import trlx_tpu.trlx as trlx

    def reward_fn(samples, prompts, outputs, **kwargs):
        return [float(len(o)) for o in outputs]

    prompts = ["ab", "cd", "ef", "gh", "ij", "kl", "mn", "op"]
    return trlx.train(reward_fn=reward_fn, prompts=prompts, config=config)


def _load_triage(path):
    with np.load(path) as npz:
        arrays = {k: npz[k] for k in npz.files}
    meta = json.loads(bytes(arrays.pop("__meta__").tobytes()).decode("utf-8"))
    return arrays, meta


def test_dynamics_stream_zero_recompiles(tmp_path):
    """A healthy run's stats stream carries the dist/* summaries, the
    rollout canary, and the health gauges — with the raw histogram arrays
    filtered out and ZERO steady-state recompiles (the fixed-bin sketch adds
    no data-dependent shapes), pinning the zero-sync/zero-recompile claim."""
    _run_health_ppo(_health_ppo_config(tmp_path))

    records = [json.loads(l) for l in open(tmp_path / "logs" / "stats.jsonl")]
    keys = set().union(*(set(r) for r in records))
    # train-step sketches (summarized host-side)
    for key in (
        "dist/log_ratio_p50",
        "dist/kl_p50",
        "dist/advantages_p50",
        "dist/value_error_p50",
        "dist/entropy_p50",
        "dist/ratio_outside_clip_frac",
    ):
        assert key in keys, f"stats stream is missing {key}"
    # rollout-side sketches + canary (uniform across collection paths)
    assert "dist/ref_kl_p50" in keys
    assert "rollout/gen_len_p50" in keys
    assert "rollout/repetition_frac" in keys
    # health gauges publish every step; a healthy tiny run is "ok"
    assert "health/verdict" in keys
    verdicts = [r["health/verdict"] for r in records if "health/verdict" in r]
    assert verdicts and all(v == 0.0 for v in verdicts)
    # the raw histogram arrays never reach the tracker stream
    assert not any(k.endswith("_hist") for k in keys)
    # the sketch-enabled step added no steady-state recompiles
    assert "recompile/train_step" not in keys
    # summaries stay inside their sketch windows
    lo, hi = SKETCH_RANGES["entropy"]
    for r in records:
        if "dist/entropy_p50" in r:
            assert lo <= r["dist/entropy_p50"] <= hi


def test_health_trip_fault_dumps_flightrec_and_triage(tmp_path):
    """Acceptance: the deterministic ``health_trip@step:1`` fault flips
    ``health/verdict``, dumps the flight record, and writes a bounded,
    reloadable ``triage/step1.npz`` carrying the offending microbatch —
    tokens, masks, advantages, and per-token logprob deltas."""
    config = _health_ppo_config(tmp_path).evolve(
        resilience=dict(fault_plan="health_trip@step:1"),
    )
    _run_health_ppo(config)

    # the verdict flipped on the injected step (and only there)
    records = [json.loads(l) for l in open(tmp_path / "logs" / "stats.jsonl")]
    tripped = [r for r in records if r.get("health/verdict") == 1.0]
    assert tripped, "health/verdict never flipped"

    # flight record dumped with the health_trip reason, carrying the
    # structured health event and the triage event
    doc = json.load(open(tmp_path / "logs" / "flightrec.json"))
    assert "health_trip" in doc["reason"]
    kinds = {r["kind"] for r in doc["records"]}
    assert "health" in kinds
    assert "triage" in kinds
    health_evt = next(r for r in doc["records"] if r["kind"] == "health")
    assert health_evt["data"]["verdict"] == "injected:fault_plan"

    # the triage artifact is bounded, atomic (no .tmp leftover), reloadable
    triage_dir = tmp_path / "logs" / "triage"
    path = triage_dir / "step1.npz"
    assert path.exists()
    assert not list(triage_dir.glob("*.tmp*"))
    arrays, meta = _load_triage(path)
    assert meta["step"] == 1
    assert meta["reason"] == "health:injected:fault_plan"
    for key in ("query_tensors", "response_tensors", "response_mask", "logprobs"):
        assert key in arrays, f"triage npz missing {key}"
    # derived quantities: GAE advantages/returns and per-token logprob deltas
    for key in ("advantages", "returns", "logprob_deltas"):
        assert key in arrays, f"triage npz missing derived {key}"
    assert arrays["logprob_deltas"].shape == arrays["logprobs"].shape
    rows = arrays["response_mask"].shape[0]
    assert rows == meta["rows"] and rows <= 64
    # the triage counter rode the stream
    keys = set().union(*(set(r) for r in records))
    assert "health/triage_dumps" in keys


def test_a_trip_after_the_first_step_compiles_nothing(tmp_path):
    """A PPO run builds its triage programs at the first optimizer step
    (``PPOTrainer._warm_triage``): the dump of a later trip, on a batch of
    the same shape, finds them and compiles nothing, however few steps a
    collection feeds (the health window is the default's, not the cycle's)."""
    import jax.monitoring

    import trlx_tpu.trlx as trlx
    from trlx_tpu.observability.health import DEFAULT_WINDOW

    compiles, during = [], []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None
    )

    def hook(trainer):
        dump = trainer._dump_triage

        def counted(reason, stats):
            before = len(compiles)
            path = dump(reason, stats)
            during.append((path, len(compiles) - before))
            return path

        trainer._dump_triage = counted

    config = _health_ppo_config(tmp_path).evolve(resilience=dict(fault_plan="health_trip@step:1"))
    trainer = trlx.train(reward_fn=lambda samples, prompts, outputs, **kw: [float(len(o)) for o in outputs],
                         prompts=["ab", "cd", "ef", "gh", "ij", "kl", "mn", "op"], config=config,
                         init_trainer_hook=hook)
    assert trainer.obs.health.window == DEFAULT_WINDOW
    (path, compiled), = during
    arrays, _ = _load_triage(path)
    assert {"advantages", "returns", "logprob_deltas"} <= set(arrays)
    assert compiled == 0


def test_update_guard_rejection_triages_batch(tmp_path):
    """A guard-rejected (injected NaN) update triages the offending batch
    through the same path — the RESILIENCE.md update-guard seam feeds the
    OBSERVABILITY.md triage artifact."""
    config = _health_ppo_config(tmp_path).evolve(
        resilience=dict(update_guard="skip", fault_plan="nan_loss@step:1"),
    )
    _run_health_ppo(config)  # skip policy: the run completes

    path = tmp_path / "logs" / "triage" / "step1.npz"
    assert path.exists()
    arrays, meta = _load_triage(path)
    assert meta["reason"] == "update_guard"
    assert "response_tensors" in arrays
    doc = json.load(open(tmp_path / "logs" / "flightrec.json"))
    assert "update guard rejected step 1" in doc["reason"]
