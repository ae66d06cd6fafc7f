"""Benchmark: PPO experience+train throughput, ppo_sentiments-shaped.

Measures end-to-end PPO samples/sec on the BASELINE.json north-star task
shape: GPT-2-small (124M, real dims, random init — no network), prompts of 64
tokens, 40 new tokens per rollout (the reference ppo_sentiments gen_kwargs,
``trlx/data/default_configs.py:54``), chunk 128, 4 PPO epochs per batch of
128. One timed unit = collect 128 rollouts (jitted KV-cache decode + scoring
fwd + hydra-ref fwd + KL) and run the 4×1 optimization steps — the same
work AcceleratePPOTrainer does per epoch (SURVEY.md §3.2-3.3).

Baseline denominator (``A100_BASELINE_SAMPLES_PER_SEC = 40``): the reference
publishes no throughput numbers (SURVEY.md §6), so this is a derived
estimate, stated openly.  Derivation: the reference ppo_sentiments config
(``trlx/data/default_configs.py:15-57``) runs 10k optimization steps of
batch 128 with ``num_rollouts=128``/``ppo_epochs=4`` — i.e. one 128-rollout
collection (128×40-token KV-cached decodes + scoring fwd + hydra-ref fwd)
per 4 updates.  An A100 runs gpt2-small (124M) batched decode at roughly
25-35ms/step at batch 128 in fp16 HF ``generate`` (memory-bound decode:
~0.25GB weights × 2 reads per token-step against ~1.5TB/s effective HBM,
plus attention/softmax and per-step host sync overhead), giving ~1.0-1.4s
per 40-token rollout chunk, ~0.4s for the two scoring forwards, and ~0.4s
for 4 updates — ≈2s per 128-sample cycle ⇒ ~55-65 samples/s upper bound,
degraded in practice by HF generate's per-step Python/host overhead and the
reference's host-side re-tokenization between decode and scoring
(``accelerate_ppo_trainer.py:329-348``) to ~40 samples/s.  ``vs_baseline`` =
samples_per_sec / 40.0 (target ≥3.0 per BASELINE.json).

One process, one JAX initialization: ``trlx_tpu.trlx.measurement_devices``
refuses any platform but ``tpu`` unless the caller pinned
``JAX_PLATFORMS=cpu`` itself (a CPU walk of the control flow, whose number
is not a device metric). Every probe raises on failure; nothing downgrades
to a message.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

import json
import os
import sys
import time

import numpy as np

A100_BASELINE_SAMPLES_PER_SEC = 40.0


_PROMPT_TOKENS = 64
_MAX_NEW = 40


def _bench_ppo_config(model_path, chunk, ckpt_dir, model_kwargs=None, parallel_kwargs=None):
    """The ppo_sentiments-shaped bench config — one definition for the
    gpt2-small headline and the gpt2-xl stage, so both measure the same
    work per sample."""
    from trlx_tpu.data.default_configs import default_ppo_config

    return default_ppo_config().evolve(
        train=dict(
            seq_length=_PROMPT_TOKENS + _MAX_NEW,
            batch_size=chunk,
            total_steps=1_000_000,
            eval_interval=1_000_000,
            checkpoint_interval=1_000_000,
            epochs=1,
            checkpoint_dir=ckpt_dir,
            tracker=None,
        ),
        model=dict(
            model_path=model_path,
            num_layers_unfrozen=2,
            **(model_kwargs or {}),
        ),
        parallel=dict(data=-1, fsdp=1, model=1, **(parallel_kwargs or {})),
        method=dict(
            num_rollouts=chunk,
            chunk_size=chunk,
            ppo_epochs=4,
            gen_kwargs=dict(
                max_new_tokens=_MAX_NEW, top_k=0, top_p=1.0, do_sample=True
            ),
        ),
    )


def _build_bench_trainer(config, reward_fn, n_prompts):
    from trlx_tpu.pipeline import get_pipeline
    from trlx_tpu.trainer import get_trainer

    trainer = get_trainer(config.train.trainer)(
        config=config, reward_fn=reward_fn, metric_fn=None, stop_sequences=[]
    )
    rng = np.random.RandomState(0)
    prompts = [
        "".join(chr(97 + c) for c in rng.randint(0, 26, _PROMPT_TOKENS))
        for _ in range(n_prompts)
    ]
    trainer.add_prompt_pipeline(
        get_pipeline(config.train.pipeline)(prompts, _PROMPT_TOKENS, trainer.tokenizer)
    )
    return trainer


def _make_cycle(trainer, config, chunk):
    """One timed unit: collect ``chunk`` rollouts + ppo_epochs update
    passes — the reference's per-epoch work (SURVEY.md §3.2-3.3)."""
    import jax

    def cycle():
        trainer.store.clear_history()
        trainer.make_experience(chunk)
        loader = trainer.store.create_loader(
            config.train.batch_size,
            shuffle=True,
            query_length=_PROMPT_TOKENS,
            response_length=_MAX_NEW,
        )
        stats = None
        for batch in loader:
            for _ in range(config.method.ppo_epochs):
                t_step = time.perf_counter()
                stats = trainer.train_step(batch)
                # the learn loop owns this counter normally; step-triggered
                # fault-plan entries (BENCH_FAULTS) key off it, so a cycle
                # must advance it too or step:N faults re-fire forever
                trainer.iter_count += 1
                # cluster-telemetry beat (docs/OBSERVABILITY.md "Distributed
                # telemetry"): the learn loop drives this at its step
                # boundaries; the bench cycle mirrors it so the headline
                # carries cluster/step_skew_s (0.0 single-process —
                # max-min over one rank — nonzero on a real pod)
                trainer.obs.cluster.note_step(time.perf_counter() - t_step)
                trainer.obs.cluster.beat(False, step=trainer.iter_count)
        jax.block_until_ready(trainer.state.params)
        return stats

    return cycle


def _program_cycle_flops(config, trainer, chunk):
    """Total FLOPs of one cycle from XLA's cost_analysis of the exact
    compiled generate/score/train_step programs (attention, collectives,
    everything — shared by the headline and xl MFU so they are comparable).
    None when unavailable or nonsensical (the cost model's missing-key
    sentinel is negative).

    The per-device × n_dev accounting is only valid when the batch fully
    shards over the data axes — a replicated batch makes every device
    recompute the same work and the multiply would inflate MFU by up to
    n_dev×. Refuse (None) rather than report a flattering wrong number.
    """
    import jax

    dp = trainer.mesh.shape.get("data", 1) * trainer.mesh.shape.get("fsdp", 1)
    if chunk % dp:
        print(
            f"bench: program-flops MFU skipped (chunk {chunk} does not shard "
            f"over data axes {dp}; per-device accounting would overcount)",
            file=sys.stderr,
        )
        return None
    try:
        from trlx_tpu.perf import hot_program_costs

        costs = hot_program_costs(
            config,
            batch_size=chunk,
            prompt_len=_PROMPT_TOKENS,
            gen_len=_MAX_NEW,
            trainer=trainer,
        )
        flops = (
            costs["generate"]["flops"]
            + costs["score"]["flops"]
            + config.method.ppo_epochs * costs["train_step"]["flops"]
        ) * max(len(jax.devices()), 1)  # cost_analysis is per device
        return flops if flops > 0 else None
    except Exception as e:  # never let accounting kill the artifact
        print(f"bench: program-flops unavailable: {e}", file=sys.stderr)
        return None


def _maybe_xl_stage(on_cpu, peak, reward_fn):
    """On-chip second point at real scale: gpt2-xl (1.5B) e2e PPO cycle on
    the same task shape (round-4 verdict next#1 — a bench window must
    capture more than gpt2-small). Runs strictly AFTER the headline stdout
    line is emitted, so an overrun can only cost this stage. Skipped on the
    CPU, on low remaining budget (``BENCH_XL_DEADLINE_S`` after
    process start), or via ``BENCH_XL=0``. Emits its own stderr JSON."""
    import jax

    if on_cpu or os.environ.get("BENCH_XL", "1") == "0":
        return
    deadline = float(os.environ.get("BENCH_XL_DEADLINE_S", "600"))
    if time.time() - _T0 > deadline:
        print(
            f"bench: skipping gpt2-xl stage (past {deadline:.0f}s budget)",
            file=sys.stderr,
        )
        return
    chunk = int(os.environ.get("BENCH_XL_CHUNK", 16))
    config = _bench_ppo_config(
        "builtin:gpt2-xl",
        chunk,
        "/tmp/trlx_tpu_bench_xl",
        # scan_layers + remat: the 20B-path compile/memory regime,
        # exercised on real silicon at 1.5B
        model_kwargs=dict(model_extra_kwargs=dict(scan_layers=True)),
        parallel_kwargs=dict(remat="full"),
    )
    trainer = _build_bench_trainer(config, reward_fn, n_prompts=128)
    cycle = _make_cycle(trainer, config, chunk)
    cycle()  # warmup/compile
    t0 = time.time()
    cycle()
    dt = time.time() - t0

    xl_flops = _program_cycle_flops(config, trainer, chunk)
    n_dev = max(len(jax.devices()), 1)
    xl_mfu = (
        xl_flops / dt / (peak * n_dev)
        if xl_flops is not None and np.isfinite(peak)
        else None
    )
    print(
        json.dumps(
            {
                "xl_stage": {
                    "model": "gpt2-xl (1.5B, scan_layers+remat)",
                    "samples_per_sec": round(chunk / dt, 3),
                    "tokens_per_sec": round(
                        chunk * (_PROMPT_TOKENS + _MAX_NEW) / dt, 1
                    ),
                    "mfu": round(xl_mfu, 4) if xl_mfu is not None else None,
                    "cycle_s": round(dt, 2),
                    "chunk": chunk,
                }
            }
        ),
        file=sys.stderr,
    )


def _elastic_probe(trainer):
    """Untimed shrink-restore probe (docs/RESILIENCE.md "Elastic restore"):
    save the live train state on the full mesh, restore it onto a HALVED
    mesh through the topology-manifest reshard path, and verify every leaf
    round-tripped byte-identically. On a single-device run the reshard path
    is forced via the ``topology_shrink`` fault instead — same machinery,
    same byte check. Returns "ok" / "degraded" for the headline's
    ``elastic_recovery`` field; a probe that cannot run raises."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from trlx_tpu.parallel.mesh import MESH_AXES
    from trlx_tpu.resilience import restore_state_elastic
    from trlx_tpu.resilience.faults import FaultPlan, get_active_plan, set_active_plan
    from trlx_tpu.utils.checkpoint import save_state

    t0 = time.time()
    tmp = tempfile.mkdtemp(prefix="trlx_tpu_bench_elastic_")
    try:
        ckpt = os.path.join(tmp, "checkpoint_0")
        save_state(ckpt, trainer.state, async_save=False)
        devs = jax.devices()
        n = len(devs)
        if n >= 2:
            # a replicated template on half the devices: a genuine topology
            # change (device_count halves), so the manifest mismatch drives
            # the host-side reshard
            half = Mesh(
                np.asarray(devs[: n // 2]).reshape(
                    (n // 2,) + (1,) * (len(MESH_AXES) - 1)
                ),
                MESH_AXES,
            )
            repl = NamedSharding(half, PartitionSpec())
            template = jax.tree_util.tree_map(
                lambda x: (
                    jax.device_put(jnp.zeros(x.shape, x.dtype), repl)
                    if isinstance(x, jax.Array)
                    else x
                ),
                trainer.state,
            )
            restored = restore_state_elastic(ckpt, template)
            mode = f"halved mesh ({n}->{n // 2} devices)"
        else:
            prev = get_active_plan()
            set_active_plan(FaultPlan.parse("topology_shrink@resume:1"))
            try:
                restored = restore_state_elastic(ckpt, trainer.state)
            finally:
                set_active_plan(prev)
            mode = "forced reshard (single device)"
        ok = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(
                jax.tree_util.tree_leaves(jax.device_get(restored)),
                jax.tree_util.tree_leaves(jax.device_get(trainer.state)),
            )
        )
        result = "ok" if ok else "degraded"
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(
        json.dumps(
            {
                "elastic_proof": {
                    "mode": mode,
                    "recovery": result,
                    "probe_s": round(time.time() - t0, 2),
                }
            }
        ),
        file=sys.stderr,
    )
    return result


def _flightrec_probe(trainer):
    """Untimed flight-recorder probe (docs/OBSERVABILITY.md "Flight
    recorder"): dump the forensic ring the warmup cycle populated, reload
    the JSON, and verify it actually carries span and metric records —
    proving the black box this build would leave behind on a crash is
    readable and non-empty. Returns "ok" / "degraded" for the headline's
    ``flight_recorder`` field; a probe that cannot run raises."""
    import shutil
    import tempfile

    t0 = time.time()
    tmp = tempfile.mkdtemp(prefix="trlx_tpu_bench_flightrec_")
    kinds = []
    try:
        path = trainer.obs.dump_flight_record(reason="bench probe", directory=tmp)
        ok = False
        if path:
            with open(path) as f:
                doc = json.load(f)
            records = doc.get("records", [])
            kinds = sorted({r.get("kind") for r in records})
            ok = bool(records) and "span" in kinds and "metric" in kinds
        result = "ok" if ok else "degraded"
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(
        json.dumps(
            {
                "flightrec_proof": {
                    "recovery": result,
                    "record_kinds": kinds,
                    "probe_s": round(time.time() - t0, 2),
                }
            }
        ),
        file=sys.stderr,
    )
    return result


def _serve_probe(trainer):
    """Untimed serving probe (docs/SERVING.md): start the HTTP frontend on
    the serving engine, stream one interactive request over a real socket
    (SSE deltas + done frame, stamped with the published params version),
    then push a synthetic admission flood through the real gate — proving
    this build can answer traffic while training AND shed load with 429s.
    Drains the frontend before returning so the pump thread never competes
    with the timed cycles. Returns "ok" / "degraded" for the headline's
    ``serving`` field; a probe that cannot run raises."""
    import http.client

    t0 = time.time()
    try:
        trainer._maybe_start_serving()
        srv = trainer._serve
        if srv is None:
            raise RuntimeError("serve frontend did not start")
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=120)
        conn.request(
            "POST",
            "/v1/generate",
            json.dumps(
                {
                    "prompt_ids": list(range(5, 21)),
                    "seed": 7,
                    "stream": True,
                    "class": "interactive",
                }
            ),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        status = resp.status
        raw = resp.read().decode()
        conn.close()
        streamed, done = 0, None
        for frame in raw.split("\n\n"):
            if not frame.startswith("data: "):
                continue
            payload = json.loads(frame[len("data: "):])
            if "tokens" in payload:
                streamed += len(payload["tokens"])
            elif payload.get("done"):
                done = payload
        flood_rejected = srv.flood_drill()
        flat = srv.flat_metrics()
        ok = (
            status == 200
            and done is not None
            and done.get("n_tokens", 0) > 0
            and streamed == done["n_tokens"]
            and done.get("params_version") is not None
            and flat.get("serve/completed", 0) >= 1
            and flood_rejected > 0
        )
        proof = {
            "http_status": status,
            "streamed_tokens": streamed,
            "params_version": done.get("params_version") if done else None,
            "flood_rejected": flood_rejected,
            "ttft_s": (
                round(float(flat["serve/ttft_p95"]), 4)
                if flat.get("serve/ttft_p95") is not None
                else None
            ),
        }
        result = "ok" if ok else "degraded"
    finally:
        # tear the frontend down NOW: the timed cycles must not share the
        # host with the serve pump (trainer shutdown re-drains a no-op)
        serve, trainer._serve = trainer._serve, None
        if serve is not None:
            serve.drain()
    proof["recovery"] = result
    proof["probe_s"] = round(time.time() - t0, 2)
    print(json.dumps({"serve_proof": proof}), file=sys.stderr)
    return result


_T0 = time.time()


def main():
    import jax

    from trlx_tpu.trlx import initialize_runtime, measurement_devices

    global _T0
    _T0 = time.time()
    initialize_runtime()
    devices, on_cpu = measurement_devices()
    # self-documenting provenance: device kind + timestamp ride the stderr
    # artifact so a bench capture alone is attributable evidence
    print(
        json.dumps(
            {
                "bench_env": {
                    "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                    "platform": devices[0].platform,
                    "device_kind": getattr(devices[0], "device_kind", "?"),
                    "n_devices": len(devices),
                }
            }
        ),
        file=sys.stderr,
    )

    import trlx_tpu.trainer.ppo  # noqa: F401
    import trlx_tpu.pipeline.offline_pipeline  # noqa: F401

    n_dev = len(devices)
    # a CPU walk (the caller pinned JAX_PLATFORMS=cpu) shrinks the timed
    # unit; its number is tagged and is not a device metric
    chunk = int(os.environ.get("BENCH_CHUNK", 16 if on_cpu else 128))
    # byte-level prompts, 64 tokens each; bucketing keeps one compiled shape
    prompt_tokens = _PROMPT_TOKENS
    max_new = _MAX_NEW

    config = _bench_ppo_config("builtin:gpt2-small", chunk, "/tmp/trlx_tpu_bench")
    # BENCH_CB=1: run rollouts through the continuous-batching engine (the
    # headline default stays the serial sampler so values remain comparable
    # across rounds; the dedicated A/B lives in
    # `python -m trlx_tpu.benchmark continuous-batching`)
    bench_cb = os.environ.get("BENCH_CB", "0") == "1"
    if bench_cb:
        config = config.evolve(train=dict(continuous_batching=True))
    # BENCH_ENGINE=1: continuous batching over the paged-KV engine with the
    # prefix cache (docs/PERFORMANCE.md engine section) — the headline then
    # carries prefix_hit_rate and kv_blocks_in_use; the dedicated A/B lives
    # in `python -m trlx_tpu.benchmark engine-paged`. BENCH_DECODE_KERNEL
    # selects the paged decode compute (xla | pallas — the in-place
    # paged-attention kernel, docs/PERFORMANCE.md "Pallas kernels").
    bench_engine = os.environ.get("BENCH_ENGINE", "0") == "1"
    if bench_engine:
        config = config.evolve(
            train=dict(continuous_batching=True),
            engine=dict(
                backend="paged", prefix_cache=True,
                decode_kernel=os.environ.get("BENCH_DECODE_KERNEL", "xla"),
            ),
        )

    # BENCH_SPEC=1: speculative continuous batching over the paged engine
    # (engine.speculative, docs/PERFORMANCE.md "Speculative continuous
    # batching") — a tiny draft proposes gamma tokens per round, the policy
    # verifies them in ONE paged forward, per-row RNG keeps every stream
    # bit-identical to a solo speculative run. The headline then carries
    # spec_acceptance_rate; the dedicated A/B lives in
    # `python -m trlx_tpu.benchmark engine-spec`.
    bench_spec = os.environ.get("BENCH_SPEC", "0") == "1"
    if bench_spec:
        config = config.evolve(
            train=dict(continuous_batching=True),
            model=dict(draft_model_path="builtin:gpt2-test", draft_gamma=4),
            engine=dict(backend="paged", prefix_cache=True, speculative=4),
            method=dict(
                gen_kwargs=dict(
                    max_new_tokens=_MAX_NEW, top_k=0, top_p=1.0,
                    do_sample=True, per_row_rng=True,
                )
            ),
        )

    # BENCH_LOSS_KERNEL: learner-step loss compute (xla | pallas). pallas
    # runs GAE + advantage whitening + the clipped PPO losses as ONE fused
    # Pallas program per train step (method.loss_kernel,
    # docs/PERFORMANCE.md "Fused learner kernels") — bit-identical
    # loss/grads/stats to the staged default. The dedicated A/B lives in
    # `python -m trlx_tpu.benchmark loss-kernel`.
    bench_loss_kernel = os.environ.get("BENCH_LOSS_KERNEL", "xla")
    if bench_loss_kernel != "xla":
        config = config.evolve(method=dict(loss_kernel=bench_loss_kernel))

    # BENCH_ASYNC=1: route experience collection through the disaggregated
    # actor/learner split (docs/ASYNC_RL.md) — one actor thread generates
    # the NEXT cycle's rollouts while the timed cycle's ppo_epochs updates
    # run, gated at max_staleness = updates-per-cycle (full overlap, bounded
    # off-policyness). The headline then carries actor_idle_frac and
    # mean_staleness; the committed A/B lives in benchmarks/ASYNC_RL_cpu.json
    # (scripts/bench_async_ab.py).
    bench_async = os.environ.get("BENCH_ASYNC", "0") == "1"
    if bench_async:
        updates_per_cycle = 4  # ppo_epochs × (num_rollouts // batch_size)
        config = config.evolve(
            async_rl=dict(
                enabled=True, mode="thread", num_actors=1,
                max_staleness=updates_per_cycle,
                # default to the collective fleet transport so the headline
                # measures the dissemination tree (BENCH_ASYNC_TRANSPORT=file
                # falls back to the in-memory/file channel); the committed
                # file-vs-collective A/B is benchmarks/ASYNC_TRANSPORT_cpu.json
                transport=os.environ.get("BENCH_ASYNC_TRANSPORT", "collective"),
            ),
            method=dict(iw_correction="clip"),
        )

    # BENCH_SERVE=1: stand up the serving frontend (docs/SERVING.md) on the
    # paged continuous-batching engine — the untimed _serve_probe then
    # streams a real HTTP request end-to-end and runs an admission flood
    # drill before the timed cycles (the frontend is drained first, so the
    # pump never competes with the timed rollouts). The committed A/B lives
    # in benchmarks/SERVE_cpu.json (scripts/bench_serve_ab.py).
    bench_serve = os.environ.get("BENCH_SERVE", "0") == "1"
    if bench_serve:
        config = config.evolve(
            train=dict(continuous_batching=True),
            engine=dict(backend="paged", prefix_cache=True),
            serve=dict(
                enabled=True, host="127.0.0.1", port=0, slots=2,
                max_new_tokens=8, host_tier_blocks=64,
                retain_param_versions=2,
            ),
        )

    # BENCH_FAULTS=1 (default): prove end-to-end recovery on this exact
    # build during the UNTIMED warmup cycle (docs/RESILIENCE.md) — the
    # fault plan fails the first two reward_fn attempts (absorbed by
    # retry/backoff) and poisons the first train step's loss to NaN
    # (absorbed by the on-device update guard). Neither fault can reach the
    # timed cycles: the plan's triggers are spent at call 1-2 / step 0.
    bench_faults = os.environ.get("BENCH_FAULTS", "1") == "1"
    if bench_faults:
        config = config.evolve(
            resilience=dict(
                update_guard="skip",  # the NaN step must not touch weights
                fault_plan="reward_raise@call:1*2; nan_loss@step:0",
                reward_backoff_s=0.05,
            )
        )

    def reward_fn(samples, prompts, outputs, **kwargs):
        return [float(sum(c in "aeiou" for c in o)) for o in outputs]

    trainer = _build_bench_trainer(config, reward_fn, n_prompts=512)
    one_cycle = _make_cycle(trainer, config, chunk)

    one_cycle()  # warmup: compiles decode, score, train programs
    fault_recovery = None
    if bench_faults:
        # the warmup just survived an injected reward outage and a NaN
        # loss; verify both recoveries actually happened before timing
        import jax

        snap = trainer.obs.metrics.snapshot(reset_histograms=False)
        retried = snap.get("resilience/reward_retries", 0) >= 2
        finite = all(
            bool(np.isfinite(np.asarray(leaf)).all())
            for leaf in jax.tree_util.tree_leaves(
                jax.device_get(trainer.state.params)
            )
        )
        fault_recovery = "ok" if (retried and finite) else "degraded"
        print(
            json.dumps(
                {
                    "fault_proof": {
                        "reward_retries": snap.get("resilience/reward_retries", 0),
                        "params_finite_after_nan_step": finite,
                        "recovery": fault_recovery,
                    }
                }
            ),
            file=sys.stderr,
        )
    elastic_recovery = _elastic_probe(trainer) if bench_faults else None
    flight_recorder = _flightrec_probe(trainer) if bench_faults else None
    serving = _serve_probe(trainer) if bench_serve else None
    n_cycles = int(os.environ.get("BENCH_CYCLES", 1 if on_cpu else 3))
    t0 = time.time()
    for _ in range(n_cycles):
        stats = one_cycle()
    dt = time.time() - t0

    samples_per_sec = n_cycles * chunk / dt
    per_chip = samples_per_sec / max(n_dev, 1)
    tag = " [cpu]" if on_cpu else ""
    if bench_cb:
        tag += " [continuous-batching]"
    if bench_async:
        tag += " [async-rl]"
    if bench_serve:
        tag += " [serve]"
    if bench_loss_kernel != "xla":
        tag += f" [loss-kernel-{bench_loss_kernel}]"
    # REAL MFU from the compiled programs (stderr; stdout stays the one-line
    # contract): XLA's cost_analysis of the exact generate/score/train_step
    # programs this bench executed — attention, collectives, everything —
    # instead of the hand-derived 2N/6N bound below. The programs are
    # already compiled (warmup), so lowering again is a cache hit.
    program_flops = (
        _program_cycle_flops(config, trainer, chunk) if not on_cpu else None
    )

    # Analytic MFU estimate (stderr; stdout stays the one-line contract).
    # Scaling-book accounting: forward ≈ 2·N FLOPs/token, backward ≈ 4·N
    # over the trainable fraction. Tokens per cycle: decode (prefill P +
    # N_new single-token steps), the scoring fwd (policy full + hydra ref
    # branch ≈ unfrozen fraction), and ppo_epochs train fwd+bwd. Attention
    # FLOPs (~3% at these shapes) excluded — a lower bound.
    n_params = sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(trainer.state.params)
    )
    seq = prompt_tokens + max_new
    n_unfrozen = config.model.num_layers_unfrozen
    unfrozen_frac = (
        1.0 if n_unfrozen < 0 else n_unfrozen / trainer.tcfg.num_layers
    )  # -1 sentinel = all layers trainable (mirrors _scan_layer_vector)
    tok = chunk * seq
    fwd = 2 * n_params
    cycle_flops = (
        tok * fwd  # decode (prefill + steps, cache makes each token one fwd)
        + tok * fwd * (1 + unfrozen_frac)  # scoring fwd + hydra ref branch
        + config.method.ppo_epochs * tok * (fwd + 2 * fwd * unfrozen_frac)
    )
    # bf16 peak per chip from the table the runtime MFU metric uses; an
    # unknown TPU device_kind raises there. The CPU has no peak: mfu is null.
    from trlx_tpu.observability.metrics import device_peak_flops

    peak = float("nan") if on_cpu else device_peak_flops(devices[0])
    mfu = cycle_flops * n_cycles / dt / (peak * max(n_dev, 1))
    mfu_real = (
        program_flops * n_cycles / dt / (peak * max(n_dev, 1))
        if program_flops is not None
        else float("nan")
    )
    print(
        json.dumps(
            {
                "mfu": round(mfu_real, 4) if np.isfinite(mfu_real) else None,
                "mfu_estimate": round(mfu, 4) if np.isfinite(mfu) else None,
                "samples_per_sec_per_chip": round(per_chip, 3),
                "cycle_tflops": round(cycle_flops / 1e12, 3),
                "program_cycle_tflops": (
                    round(program_flops / 1e12, 3)
                    if program_flops is not None
                    else None
                ),
                "note": (
                    "mfu = XLA cost_analysis flops of the executed "
                    "generate/score/train programs; mfu_estimate = analytic "
                    "2N/6N lower bound, attention excluded"
                ),
            }
        ),
        file=sys.stderr,
    )
    line = {
        "metric": "ppo_sentiments-shaped e2e throughput (gpt2-small, 64+40 tok)" + tag,
        "value": round(samples_per_sec, 3),
        "unit": "samples/sec",
        "vs_baseline": round(samples_per_sec / A100_BASELINE_SAMPLES_PER_SEC, 3),
        # observability-layer throughput fields (docs/OBSERVABILITY.md):
        # whole-sequence tokens per wall-second, and measured MFU from the
        # executed programs' XLA cost_analysis (null when no cost model)
        "tokens_per_sec": round(samples_per_sec * seq, 1),
        "mfu": round(mfu_real, 4) if np.isfinite(mfu_real) else None,
    }
    # rollout-pipeline overlap (docs/PERFORMANCE.md): fraction of the last
    # cycle's rollout wall-time in which host reward scoring was hidden
    # behind device generation (0.0 on the depth-0 serial path)
    overlap = trainer.make_experience_stats.get("throughput/rollout_overlap_frac")
    line["rollout_overlap_frac"] = (
        round(float(overlap), 4) if overlap is not None else None
    )
    # decode slot utilization (docs/PERFORMANCE.md): live slot-steps ÷ total
    # slot-steps of the last cycle's rollout decode. On the chunked paths it
    # is mask-derived (1 − batch-tail padding waste); with
    # train.continuous_batching (BENCH_CB=1) it comes from the slot-refill
    # engine's exact counters.
    slot_util = trainer.make_experience_stats.get("throughput/slot_utilization")
    line["slot_utilization"] = (
        round(float(slot_util), 4) if slot_util is not None else None
    )
    # paged-engine gauges (docs/PERFORMANCE.md): prefix-cache hit rate over
    # full prompt blocks and the block pool's high-water, from the last
    # cycle's rollout engine; null unless BENCH_ENGINE=1 selected the paged
    # backend (+ prefix cache)
    hit_rate = trainer.make_experience_stats.get("engine/prefix_hit_rate")
    line["prefix_hit_rate"] = (
        round(float(hit_rate), 4) if hit_rate is not None else None
    )
    blocks = trainer.make_experience_stats.get("engine/kv_blocks_in_use")
    line["kv_blocks_in_use"] = int(blocks) if blocks is not None else None
    # speculative-decoding gauge (docs/PERFORMANCE.md "Speculative
    # continuous batching"): fraction of draft proposals the target
    # accepted over the last cycle's collection; null unless BENCH_SPEC=1
    acc = trainer.make_experience_stats.get("engine/spec_acceptance_rate")
    line["spec_acceptance_rate"] = (
        round(float(acc), 4) if acc is not None else None
    )
    # async actor/learner gauges (docs/ASYNC_RL.md): fraction of the actor
    # fleet's wall-time spent waiting (staleness gate + queue back-pressure)
    # and the mean consumption staleness in learner updates, from the last
    # cycle's collection; null unless BENCH_ASYNC=1
    idle = trainer.make_experience_stats.get("async/actor_idle_frac")
    line["actor_idle_frac"] = round(float(idle), 4) if idle is not None else None
    stale = trainer.make_experience_stats.get("async/staleness_mean")
    line["mean_staleness"] = round(float(stale), 4) if stale is not None else None
    # collective fleet-transport gauges (docs/ASYNC_RL.md "Transports"):
    # ack-measured dissemination-tree latency and the learner's delta-publish
    # egress for the last cycle's collection; null unless BENCH_ASYNC=1 with
    # the collective transport
    diss = trainer.make_experience_stats.get("async/dissemination_latency_s")
    line["dissemination_latency_s"] = (
        round(float(diss), 6) if diss is not None else None
    )
    pub = trainer.make_experience_stats.get("async/publish_bytes")
    line["publish_bytes"] = int(pub) if pub is not None else None
    # resilience proof (docs/RESILIENCE.md): "ok" when the warmup cycle's
    # injected reward outage was retried away AND the injected NaN step left
    # the weights finite (update guard); null when BENCH_FAULTS=0
    line["fault_recovery"] = fault_recovery
    # elastic proof (docs/RESILIENCE.md "Elastic restore"): "ok" when the
    # untimed shrink-restore probe round-tripped the train state through a
    # halved mesh (or, single-device, through the forced reshard path)
    # byte-identically; null when BENCH_FAULTS=0
    line["elastic_recovery"] = elastic_recovery
    # flight-recorder proof (docs/OBSERVABILITY.md "Flight recorder"): "ok"
    # when the untimed dump+reload probe found span AND metric records in
    # the ring the warmup populated; null when BENCH_FAULTS=0
    line["flight_recorder"] = flight_recorder
    # serving proof (docs/SERVING.md): "ok" when the untimed probe streamed
    # a real HTTP request end-to-end off the published params AND the
    # admission flood drill shed load with 429s; null when BENCH_SERVE=0
    line["serving"] = serving
    # RL health verdict (docs/OBSERVABILITY.md "Training dynamics"): "ok"
    # or the first tripped detector at the end of the timed cycles — a
    # degenerate-run artifact is labeled as such, not read as a perf number
    try:
        line["health"] = str(trainer.obs.health.verdict)
    except Exception:
        line["health"] = None
    # cross-rank step skew (docs/OBSERVABILITY.md "Distributed telemetry"):
    # max−min per-rank step time at the last cluster beat — 0.0 on a
    # single process, the straggler signal on a pod
    skew = trainer.obs.metrics.snapshot(reset_histograms=False).get(
        "cluster/step_skew_s"
    )
    line["step_skew_s"] = round(float(skew), 4) if skew is not None else None
    line["platform"] = devices[0].platform
    line["device_kind"] = devices[0].device_kind
    line["n_devices"] = n_dev
    # the headline contract is emitted BEFORE the optional xl stage: an
    # xl-stage overrun (or external kill) can only cost the extra point,
    # never the artifact the driver parses
    print(json.dumps(line), flush=True)

    # drop the 124M trainer (params, optimizer state, hydra ref, rollout
    # store) before the 1.5B build — on a single chip the two don't need to
    # coexist in HBM. The cycle closure captures the trainer, so it must be
    # dropped too. Async actor threads must stop first (they hold params).
    trainer._shutdown_collectors()
    trainer = None
    one_cycle = None
    _maybe_xl_stage(on_cpu, peak, reward_fn)


if __name__ == "__main__":
    sys.exit(main())
