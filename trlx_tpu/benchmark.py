"""Benchmark suite + A-vs-B comparator.

Capability parity with the reference's empirical regression mechanism —
``scripts/benchmark.sh:1-62`` (fixed task list at fixed seeds, metrics
logged per step) plus ``trlx/reference.py:1-103`` (branch-vs-main report) —
rebuilt for offline TPU use: every task's stats stream to a JSONL file via
the built-in jsonl tracker, and the comparator renders a markdown report of
final/mean metric deltas between two runs instead of a W&B report.

Usage::

    python scripts/benchmark.py run --output-dir benchmarks/main --scale ci
    python scripts/benchmark.py run --output-dir benchmarks/branch --scale ci
    python scripts/benchmark.py report benchmarks/main benchmarks/branch

Suite (same shape as ``benchmark.sh:40-62``): randomwalks PPO + ILQL (the
CPU-scale anchors) and the sentiment quartet (PPO / ILQL / SFT / PPO-T5).
``--scale ci`` shrinks every task to smoke size; ``--scale full`` runs the
example defaults.
"""

import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

from trlx_tpu.utils import get_git_tag, logging

logger = logging.get_logger(__name__)

_EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples")

# Fixed seeds: runs are comparable across branches (benchmark.sh pins its
# tasks the same way via the examples' default configs).
_SEED = 1000


def provenance() -> Dict[str, Any]:
    """Backend/toolchain provenance block stamped into every A/B artifact.

    The bench entry points run on whatever backend JAX selected and used
    to record only a bare ``backend`` string — an artifact produced by a
    silent CPU fallback was indistinguishable from a chip run at a glance
    (ROADMAP: "all perf evidence is CPU-scale with no way to tell from the
    artifact"). Every measure_* function now embeds this block, and
    ``scripts/stamp_benchmark_provenance.py`` retrofits committed
    artifacts.
    """
    import platform

    import jax

    dev = jax.devices()[0]
    return {
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "num_devices": jax.device_count(),
        "jax_version": jax.__version__,
        "python_version": platform.python_version(),
        # UTC ISO-8601 Z — the repo's artifact timestamp convention
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }

# task name → (script path, CI-scale hparam overrides)
TASKS: Dict[str, Tuple[str, Dict[str, Any]]] = {
    "ppo_randomwalks": (
        os.path.join(_EXAMPLES, "randomwalks", "ppo_randomwalks.py"),
        {
            "train.total_steps": 4, "train.batch_size": 8, "train.eval_interval": 2,
            "method.num_rollouts": 8, "method.chunk_size": 8, "method.ppo_epochs": 1,
        },
    ),
    "ilql_randomwalks": (
        os.path.join(_EXAMPLES, "randomwalks", "ilql_randomwalks.py"),
        {"train.total_steps": 4, "train.batch_size": 8, "train.eval_interval": 2},
    ),
    "ppo_sentiments": (
        os.path.join(_EXAMPLES, "ppo_sentiments.py"),
        {
            "train.total_steps": 2, "train.batch_size": 4, "train.eval_interval": 2,
            "train.seq_length": 32, "method.num_rollouts": 4, "method.chunk_size": 4,
            "method.ppo_epochs": 1, "method.gen_kwargs.max_new_tokens": 8,
            "model.model_path": "builtin:gpt2-test", "tokenizer.tokenizer_path": "builtin:bytes",
        },
    ),
    "ilql_sentiments": (
        os.path.join(_EXAMPLES, "ilql_sentiments.py"),
        {
            "train.total_steps": 2, "train.batch_size": 4, "train.eval_interval": 2,
            "train.seq_length": 32,
            "model.model_path": "builtin:gpt2-test", "tokenizer.tokenizer_path": "builtin:bytes",
        },
    ),
    "sft_sentiments": (
        os.path.join(_EXAMPLES, "sft_sentiments.py"),
        {
            "train.total_steps": 2, "train.batch_size": 4, "train.eval_interval": 2,
            "train.seq_length": 32,
            "model.model_path": "builtin:gpt2-test", "tokenizer.tokenizer_path": "builtin:bytes",
        },
    ),
    "ppo_sentiments_t5": (
        os.path.join(_EXAMPLES, "ppo_sentiments_t5.py"),
        {
            "train.total_steps": 2, "train.batch_size": 4, "train.eval_interval": 2,
            "train.seq_length": 32, "method.num_rollouts": 4, "method.chunk_size": 4,
            "method.ppo_epochs": 1, "method.gen_kwargs.max_new_tokens": 8,
            "model.model_path": "builtin:t5-test", "tokenizer.tokenizer_path": "builtin:bytes",
        },
    ),
    "grpo_sentiments": (
        os.path.join(_EXAMPLES, "grpo_sentiments.py"),
        {
            "train.total_steps": 2, "train.batch_size": 8, "train.eval_interval": 2,
            "train.seq_length": 56, "method.num_rollouts": 8, "method.chunk_size": 8,
            "method.group_size": 4, "method.ppo_epochs": 1,
            "model.model_path": "builtin:gpt2-test", "tokenizer.tokenizer_path": "builtin:bytes",
        },
    ),
    "dpo_sentiments": (
        os.path.join(_EXAMPLES, "dpo_sentiments.py"),
        {
            "train.total_steps": 2, "train.batch_size": 4, "train.eval_interval": 2,
            "train.seq_length": 48, "method.gen_kwargs.max_new_tokens": 8,
            "model.model_path": "builtin:gpt2-test", "tokenizer.tokenizer_path": "builtin:bytes",
        },
    ),
    "grpo_moe_mixtral": (
        os.path.join(_EXAMPLES, "grpo_moe_mixtral.py"),
        {
            "train.total_steps": 2, "train.batch_size": 8, "train.eval_interval": 2,
            "train.seq_length": 56, "method.num_rollouts": 8, "method.chunk_size": 8,
            "method.group_size": 4, "method.ppo_epochs": 1,
            "method.gen_kwargs.max_new_tokens": 8,
        },
    ),
    "ppo_speculative": (
        os.path.join(_EXAMPLES, "ppo_speculative.py"),
        {
            "train.total_steps": 2, "train.batch_size": 8, "train.eval_interval": 2,
            "train.seq_length": 48, "method.num_rollouts": 8, "method.chunk_size": 8,
            "method.ppo_epochs": 1, "method.gen_kwargs.max_new_tokens": 8,
            "model.model_path": "builtin:gpt2-test", "tokenizer.tokenizer_path": "builtin:bytes",
        },
    ),
}


def run_task(
    name: str,
    output_dir: str,
    scale: str = "ci",
    extra_env: Optional[Dict[str, str]] = None,
    timeout: Optional[float] = None,
) -> Dict[str, Any]:
    """Run one suite task as a subprocess; stats land in
    ``<output_dir>/<name>/stats.jsonl``; returns the task record."""
    script, ci_overrides = TASKS[name]
    task_dir = os.path.join(output_dir, name)
    os.makedirs(task_dir, exist_ok=True)
    hparams: Dict[str, Any] = {
        "train.seed": _SEED,
        "train.tracker": "jsonl",
        "train.logging_dir": task_dir,
        "train.checkpoint_dir": os.path.join(task_dir, "ckpts"),
        "train.checkpoint_interval": 10_000_000,
        "train.save_best": False,
    }
    if scale == "ci":
        hparams.update(ci_overrides)

    env = dict(os.environ)
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(p for p in (pkg_root, env.get("PYTHONPATH")) if p)
    if extra_env:
        env.update(extra_env)

    t0 = time.time()
    with open(os.path.join(task_dir, "run.log"), "w") as log:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(script), json.dumps(hparams)],
            cwd=os.path.dirname(os.path.abspath(script)),
            env=env,
            stdout=log,
            stderr=subprocess.STDOUT,
            timeout=timeout,
        )
    record = {
        "task": name,
        "rc": proc.returncode,
        "runtime_s": round(time.time() - t0, 1),
        "stats_path": os.path.join(task_dir, "stats.jsonl"),
    }
    throughput = _throughput_summary(record["stats_path"])
    if throughput:
        record["throughput"] = throughput
    logger.info(f"benchmark {name}: rc={proc.returncode} ({record['runtime_s']}s)")
    return record


_THROUGHPUT_KEYS = (
    "throughput/tokens_per_sec",
    "throughput/samples_per_sec",
    "throughput/mfu",
    "throughput/rollout_overlap_frac",
    "throughput/rollout_tokens_per_sec",
    "throughput/slot_utilization",
    "rollout/padded_decode_frac",
    "time/train_step",
    "time/rollout",
    "time/rollout_host",
)


def _throughput_summary(stats_path: str) -> Dict[str, float]:
    """Mean of the observability layer's per-step throughput fields over a
    task's stats stream — rides the suite's ``meta.json`` record so an A/B
    comparison carries speed context, not just metric curves."""
    if not os.path.exists(stats_path):
        return {}
    series: Dict[str, List[float]] = {}
    with open(stats_path) as f:
        for line in f:
            if not line.strip():
                continue
            try:
                record = json.loads(line)
            except ValueError:
                continue
            for key in _THROUGHPUT_KEYS:
                value = record.get(key)
                if isinstance(value, (int, float)):
                    series.setdefault(key, []).append(float(value))
    return {k: round(sum(v) / len(v), 6) for k, v in series.items()}


def run_suite(
    output_dir: str,
    tasks: Optional[List[str]] = None,
    scale: str = "ci",
    extra_env: Optional[Dict[str, str]] = None,
    timeout: Optional[float] = None,
) -> List[Dict[str, Any]]:
    os.makedirs(output_dir, exist_ok=True)
    branch, commit = get_git_tag()
    meta = {"branch": branch, "commit": commit, "scale": scale, "time": time.strftime("%F %T")}
    records = [
        run_task(name, output_dir, scale, extra_env, timeout)
        for name in (tasks or list(TASKS))
    ]
    meta["tasks"] = records
    with open(os.path.join(output_dir, "meta.json"), "w") as f:
        json.dump(meta, f, indent=2)
    return records


def _load_stats(run_dir: str, task: str) -> List[Dict[str, Any]]:
    path = os.path.join(run_dir, task, "stats.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


_KEY_METRICS = (
    "reward/mean", "metrics/optimality", "metrics/sentiments",
    "losses/total_loss", "losses/loss",
    "throughput/tokens_per_sec", "throughput/mfu",
    "throughput/rollout_overlap_frac",
    "throughput/rollout_tokens_per_sec",
    "throughput/slot_utilization",
    "rollout/padded_decode_frac",
)


def compare_runs(run_a: str, run_b: str, metrics: Optional[List[str]] = None) -> str:
    """Markdown A-vs-B report over the shared tasks of two suite runs
    (the ``trlx/reference.py:29-96`` metric-curves report, offline)."""

    def meta(run):
        path = os.path.join(run, "meta.json")
        return json.load(open(path)) if os.path.exists(path) else {}

    meta_a, meta_b = meta(run_a), meta(run_b)
    lines = [
        f"# Benchmark comparison",
        "",
        f"- A: `{run_a}` ({meta_a.get('branch')}@{meta_a.get('commit')})",
        f"- B: `{run_b}` ({meta_b.get('branch')}@{meta_b.get('commit')})",
        "",
        "| task | metric | A final | B final | Δ | A mean | B mean |",
        "|---|---|---|---|---|---|---|",
    ]
    tasks = sorted(
        {t for t in os.listdir(run_a) if os.path.isdir(os.path.join(run_a, t))}
        & {t for t in os.listdir(run_b) if os.path.isdir(os.path.join(run_b, t))}
    )
    for task in tasks:
        stats_a, stats_b = _load_stats(run_a, task), _load_stats(run_b, task)
        keys = metrics or [
            k for k in _KEY_METRICS
            if any(k in r for r in stats_a) and any(k in r for r in stats_b)
        ]
        for key in keys:
            series_a = [r[key] for r in stats_a if key in r]
            series_b = [r[key] for r in stats_b if key in r]
            if not series_a or not series_b:
                continue
            fa, fb = series_a[-1], series_b[-1]
            ma = sum(series_a) / len(series_a)
            mb = sum(series_b) / len(series_b)
            lines.append(
                f"| {task} | {key} | {fa:.4g} | {fb:.4g} | {fb - fa:+.4g} | {ma:.4g} | {mb:.4g} |"
            )
    return "\n".join(lines) + "\n"


def measure_speculative(
    policy_layers: int = 24,
    policy_hidden: int = 256,
    gamma: int = 4,
    batch_size: int = 8,
    prompt_len: int = 16,
    max_new_tokens: int = 32,
    rounds: int = 8,
    seed: int = _SEED,
) -> Dict[str, Any]:
    """Rollout-throughput A/B: plain sampler vs draft-and-verify speculative
    decoding (round-3 verdict weak#5 — acceptance was property-tested exact,
    but no artifact showed a wall-clock number).

    Policy: a ``policy_layers`` × ``policy_hidden`` gpt2 family model;
    draft: the stock 2-layer/64-hidden gpt2-test (same byte vocab). Both
    trainers come up through the public registry and generation runs through
    the trainer's jitted rollout path — the same program PPO's
    make_experience uses. Runs on whatever backend JAX selected, so the same
    entry produces CPU program-level ratios or on-chip numbers.

    Two caveats worth reading off the artifact rather than assuming:
    speculation wins only when the policy forward dominates (at gpt2-test
    scale the bookkeeping costs more than it saves — the committed artifact
    includes that sub-1.0 point deliberately), and the acceptance rate here
    reflects two *untrained* models' agreement — with a real distilled
    draft it is typically far higher, so the reported speedup is a floor
    for the harness, not a ceiling for the method.
    """
    import numpy as np

    from trlx_tpu.trlx import initialize_runtime

    initialize_runtime()  # honors TRLX_TPU_PLATFORM before any backend init

    import trlx_tpu.trainer.ppo  # noqa: F401  (registers PPOTrainer)
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.trainer import get_trainer

    policy_extra = dict(
        num_layers=policy_layers,
        hidden_size=policy_hidden,
        num_heads=max(4, policy_hidden // 32),
        intermediate_size=4 * policy_hidden,
    )
    results: Dict[str, Any] = {
        "config": dict(
            policy=policy_extra,
            draft=dict(num_layers=2, hidden_size=64),
            gamma=gamma,
            batch_size=batch_size,
            prompt_len=prompt_len,
            max_new_tokens=max_new_tokens,
            rounds=rounds,
        )
    }
    for mode in ("plain", "speculative"):
        model_kwargs: Dict[str, Any] = dict(
            model_path="builtin:gpt2-test",
            num_layers_unfrozen=1,
            model_extra_kwargs=dict(policy_extra),
        )
        if mode == "speculative":
            model_kwargs.update(
                draft_model_path="builtin:gpt2-test", draft_gamma=gamma
            )
        cfg = default_ppo_config().evolve(
            train=dict(
                seq_length=prompt_len + max_new_tokens,
                batch_size=batch_size,
                total_steps=1,
                checkpoint_interval=10_000_000,
                tracker=None,
                seed=seed,
            ),
            model=model_kwargs,
            tokenizer=dict(tokenizer_path="builtin:bytes"),
            method=dict(
                num_rollouts=batch_size,
                chunk_size=batch_size,
                gen_kwargs=dict(
                    max_new_tokens=max_new_tokens, top_k=0, top_p=1.0, do_sample=True
                ),
            ),
        )
        trainer = get_trainer(cfg.train.trainer)(
            cfg, reward_fn=lambda **kw: [0.0] * batch_size
        )
        rng = np.random.RandomState(seed)
        ids = rng.randint(0, 256, (batch_size, prompt_len)).astype(np.int32)
        mask = np.ones_like(ids)
        out = trainer.generate(ids, mask)  # compile warmup, excluded from timing
        import jax

        jax.block_until_ready(out.sequences)
        t0 = time.time()
        for _ in range(rounds):
            out = trainer.generate(ids, mask)
        jax.block_until_ready(out.sequences)
        dt = time.time() - t0
        results[mode] = {
            "samples_per_s": round(batch_size * rounds / dt, 3),
            "tokens_per_s": round(batch_size * rounds * max_new_tokens / dt, 1),
            "seconds": round(dt, 3),
        }
        if mode == "speculative":
            results[mode].update(
                {k.split("/")[-1]: v for k, v in trainer.last_spec_stats.items()}
            )
    results["speedup"] = round(
        results["speculative"]["samples_per_s"] / results["plain"]["samples_per_s"], 3
    )
    import jax

    results["backend"] = jax.default_backend()
    results["provenance"] = provenance()
    return results


def measure_continuous_batching(
    policy_layers: int = 8,
    policy_hidden: int = 128,
    batch_size: int = 16,
    prompt_len: int = 16,
    max_new_tokens: int = 96,
    num_rollouts: int = 64,
    absorb_frac: float = 0.08,
    segment_len: int = 8,
    rounds: int = 3,
    seed: int = _SEED,
) -> Dict[str, Any]:
    """Rollout-collection A/B: serial chunked decode vs continuous batching
    (slot-refill segment decode, docs/PERFORMANCE.md) on a synthetic
    heterogeneous-response-length workload.

    Length heterogeneity is synthesized with a transition ``logit_mask``
    whose first ``absorb_frac`` of the byte vocabulary allows only eos as
    the next token: each decode step absorbs with roughly that probability,
    so response lengths are ~geometric in ``[1, max_new_tokens]`` — the
    regime where the serial path's batch-tail padding waste is largest. Both
    modes sample with per-row RNG (``gen_kwargs.per_row_rng``), so they
    decode the *same* per-prompt sequences: the tokens-per-second ratio is a
    pure scheduling comparison, not a workload change
    (tests/test_continuous_batching.py pins the store equivalence).

    Reports per mode: ``throughput/rollout_tokens_per_sec``, per-chunk
    ``time/rollout``, ``rollout/padded_decode_frac`` and
    ``throughput/slot_utilization``, plus the wall-clock speedup. Runs on
    whatever backend JAX selected (CPU program-level ratios or on-chip
    numbers).
    """
    import numpy as np

    from trlx_tpu.trlx import initialize_runtime

    initialize_runtime()  # honors TRLX_TPU_PLATFORM before any backend init

    import trlx_tpu.pipeline.offline_pipeline  # noqa: F401  (registration)
    import trlx_tpu.trainer.ppo  # noqa: F401  (registers PPOTrainer)
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.pipeline import get_pipeline
    from trlx_tpu.trainer import get_trainer

    absorb_n = max(1, int(absorb_frac * 256))
    # builtin:bytes vocab: ids 0..255 bytes, 256 bos, 257 eos, 258 pad (=259)
    vocab, eos = 259, 257
    logit_mask = np.ones((vocab, vocab), bool)
    logit_mask[:absorb_n, :] = False
    logit_mask[:absorb_n, eos] = True

    policy_extra = dict(
        num_layers=policy_layers,
        hidden_size=policy_hidden,
        num_heads=max(4, policy_hidden // 32),
        intermediate_size=4 * policy_hidden,
    )
    results: Dict[str, Any] = {
        "config": dict(
            policy=policy_extra,
            batch_size=batch_size,
            prompt_len=prompt_len,
            max_new_tokens=max_new_tokens,
            num_rollouts=num_rollouts,
            absorb_frac=absorb_frac,
            segment_len=segment_len,
            rounds=rounds,
        )
    }

    def reward_fn(samples, prompts, outputs, **kwargs):
        return [float(sum(c in "aeiou" for c in o)) for o in outputs]

    rs = np.random.RandomState(seed)
    prompts = [
        "".join(chr(97 + c) for c in rs.randint(0, 26, prompt_len))
        for _ in range(max(num_rollouts, 4 * batch_size))
    ]

    for mode in ("serial", "continuous"):
        cfg = default_ppo_config().evolve(
            train=dict(
                seq_length=prompt_len + max_new_tokens,
                batch_size=batch_size,
                total_steps=1,
                checkpoint_interval=10_000_000,
                tracker=None,
                seed=seed,
                continuous_batching=(mode == "continuous"),
                continuous_batching_segment=segment_len,
            ),
            model=dict(
                model_path="builtin:gpt2-test",
                num_layers_unfrozen=1,
                model_extra_kwargs=dict(policy_extra),
            ),
            tokenizer=dict(tokenizer_path="builtin:bytes"),
            method=dict(
                num_rollouts=num_rollouts,
                chunk_size=batch_size,
                gen_kwargs=dict(
                    max_new_tokens=max_new_tokens, top_k=0, top_p=1.0,
                    do_sample=True, per_row_rng=True,
                ),
            ),
        )
        trainer = get_trainer(cfg.train.trainer)(
            cfg, reward_fn=reward_fn, logit_mask=logit_mask
        )
        trainer.add_prompt_pipeline(
            get_pipeline(cfg.train.pipeline)(prompts, prompt_len, trainer.tokenizer)
        )
        trainer.make_experience(num_rollouts)  # compile warmup, untimed
        t0 = time.time()
        for _ in range(rounds):
            trainer.store.clear_history()
            trainer.make_experience(num_rollouts)
        dt = time.time() - t0
        es = trainer.make_experience_stats
        lengths = [
            int(np.asarray(e.response_tensor).shape[0])
            for e in trainer.store.history
        ]
        results[mode] = {
            "seconds": round(dt, 3),
            "rollout_tokens_per_sec": round(
                float(es.get("throughput/rollout_tokens_per_sec", 0.0)), 1
            ),
            "time_rollout_s": round(float(es.get("time/rollout", 0.0)), 4),
            "padded_decode_frac": round(
                float(es.get("rollout/padded_decode_frac", 0.0)), 4
            ),
            "slot_utilization": round(
                float(es.get("throughput/slot_utilization", 0.0)), 4
            ),
            "response_len_mean": round(float(np.mean(lengths)), 2) if lengths else 0.0,
            "response_len_max": int(np.max(lengths)) if lengths else 0,
        }
        if mode == "continuous":
            results[mode]["refill_prefills"] = int(
                es.get("rollout/refill_prefills", 0)
            )
            results[mode]["segments"] = int(es.get("rollout/segments", 0))
    results["speedup"] = round(
        results["serial"]["seconds"] / max(results["continuous"]["seconds"], 1e-9), 3
    )
    results["padded_frac_drop"] = round(
        results["serial"]["padded_decode_frac"]
        - results["continuous"]["padded_decode_frac"],
        4,
    )
    import jax

    results["backend"] = jax.default_backend()
    results["provenance"] = provenance()
    return results


def measure_engine_paged(
    policy_layers: int = 8,
    policy_hidden: int = 128,
    batch_size: int = 16,
    prompt_len: int = 32,
    max_new_tokens: int = 96,
    group_size: int = 8,
    n_groups: int = 8,
    passes: int = 2,
    absorb_frac: float = 0.08,
    kv_block_size: int = 8,
    segment_len: int = 8,
    seed: int = _SEED,
) -> Dict[str, Any]:
    """Engine A/B: dense per-slot KV vs paged block-pool KV + prefix cache
    (docs/PERFORMANCE.md engine section) on a shared-prefix workload —
    ``n_groups`` distinct prompts × ``group_size`` identical members (the
    GRPO-group shape) driven through the engine for ``passes`` waves with
    FIXED params (the repeated-eval shape; a trained-params wave would
    flush the prefix cache, see ``ContinuousEngine.begin_collection``).

    Responses are ~geometric in ``[1, max_new_tokens]`` via an absorbing
    transition mask, so live tokens sit far below ``slots × max_length`` —
    the regime the paged pool exists for. Both modes decode the SAME
    per-row RNG streams and the harvest is asserted bit-identical inside
    this function, so every delta is bookkeeping, never a workload change.

    The two acceptance numbers (committed: benchmarks/ENGINE_PAGED_cpu.json):

    - ``kv_bytes_high_water`` (paged) vs ``kv_cache_bytes`` (dense): the
      paged pool's high-water is blocks-in-use × block bytes — live
      tokens — while the dense cache is ``B × (P + N)`` regardless;
    - ``prefill_tokens``: prefix-cache hits prefill only unshared
      suffixes, so the paged engine prefills strictly fewer prompt tokens
      (``prefix_tokens_saved`` = the columns skipped).
    """
    import numpy as np

    from trlx_tpu.trlx import initialize_runtime

    initialize_runtime()  # honors TRLX_TPU_PLATFORM before any backend init

    import jax

    from trlx_tpu.data.configs import ModelConfig
    from trlx_tpu.engine.core import ContinuousEngine
    from trlx_tpu.models.builder import build_causal_lm
    from trlx_tpu.models.transformer import make_kv_cache
    from trlx_tpu.ops.paged_kv import PagedSpec
    from trlx_tpu.ops.sampling import (
        GenerationConfig,
        apply_transition_mask,
        per_row_keys,
    )
    from trlx_tpu.ops.slot_refill import make_slot_refill_fns

    # builtin:bytes vocab: ids 0..255 bytes, 256 bos, 257 eos, 258 pad (=259)
    vocab, eos, pad = 259, 257, 258
    absorb_n = max(1, int(absorb_frac * 256))
    trans = np.ones((vocab, vocab), bool)
    trans[:absorb_n, :] = False
    trans[:absorb_n, eos] = True
    import jax.numpy as jnp

    tmask = jnp.asarray(trans)

    def adjust(step_out, logits):
        return apply_transition_mask(tmask, step_out["last_tokens"], logits)

    policy_extra = dict(
        num_layers=policy_layers,
        hidden_size=policy_hidden,
        num_heads=max(4, policy_hidden // 32),
        intermediate_size=4 * policy_hidden,
    )
    module, params, tcfg = build_causal_lm(
        ModelConfig(
            model_path="builtin:gpt2-test", model_extra_kwargs=dict(policy_extra)
        ),
        head="value",
    )

    def apply_fn(p, ids, **kw):
        return module.apply({"params": p}, ids, **kw)

    gen_config = GenerationConfig(
        max_new_tokens=max_new_tokens, eos_token_id=eos, pad_token_id=pad,
        do_sample=True, per_row_rng=True,
    )
    B, P, N = batch_size, prompt_len, max_new_tokens
    S = P + N
    rs = np.random.RandomState(seed)
    group_prompts = rs.randint(0, 200, (n_groups, P)).astype(np.int32)
    prompts = np.repeat(group_prompts, group_size, axis=0)  # GRPO-group shape
    masks = np.ones_like(prompts)
    n = prompts.shape[0]
    key_rng = jax.random.PRNGKey(seed)
    pass_keys = []
    for _ in range(passes + 1):  # +1 warmup wave
        key_rng, call = jax.random.split(key_rng)
        pass_keys.append(np.asarray(per_row_keys(call, n)))

    TB = -(-S // kv_block_size)
    results: Dict[str, Any] = {
        "config": dict(
            policy=policy_extra, batch_size=B, prompt_len=P,
            max_new_tokens=N, group_size=group_size, n_groups=n_groups,
            passes=passes, absorb_frac=absorb_frac,
            kv_block_size=kv_block_size, segment_len=segment_len,
        )
    }
    from trlx_tpu.ops.paged_kv import dense_kv_bytes
    from trlx_tpu.perf import lowered_costs

    harvests: Dict[str, Dict[int, Any]] = {}
    # dense reference, paged with the gather/scatter decode (the
    # bit-equivalence reference), and paged with the in-place Pallas
    # decode kernel + fused sampling (engine.decode_kernel: pallas)
    arms = (("dense", None), ("paged", "xla"), ("pallas", "pallas"))
    for mode, decode_kernel in arms:
        paged = (
            PagedSpec(block_size=kv_block_size, max_blocks=1 + 2 * B * TB)
            if decode_kernel is not None
            else None
        )
        fns = make_slot_refill_fns(
            apply_fn, lambda b, s: make_kv_cache(tcfg, b, s), B, P, gen_config,
            adjust_logits=adjust, segment_len=segment_len,
            params_example=params, paged=paged,
            decode_kernel=decode_kernel or "xla",
        )
        engine = ContinuousEngine(
            fns, params, pad, prefix_cache=(paged is not None)
        )

        def wave(k, got):
            engine.enqueue_prompts(prompts, masks, pass_keys[k])
            while engine.busy:
                for c in engine.step():
                    got[c.index] = (c.tokens.tobytes(), c.logprobs.tobytes())

        wave(0, {})  # warmup: compiles refill buckets + the segment program
        engine.begin_collection(params)  # same params: prefix cache stays warm
        got: Dict[int, Any] = {}
        t0 = time.time()
        for k in range(1, passes + 1):
            wave(k, got)
        dt = time.time() - t0
        harvests[mode] = got
        st = engine.stats
        gen_tokens = st.live_slot_steps
        results[mode] = {
            "seconds": round(dt, 3),
            "rollout_tokens_per_sec": round(gen_tokens / max(dt, 1e-9), 1),
            "slot_utilization": round(st.slot_utilization, 4),
            "prefill_tokens": int(st.prefill_tokens),
        }
        # XLA's compiled cost model for the segment-decode program each arm
        # actually ran — the program-level record of the gather tax (the
        # transient dense view exists in the gather arms' programs, not in
        # the kernel arm's)
        seg_costs = lowered_costs(
            fns.decode_segment.lower(params, engine.state)
        )
        results[mode]["decode_segment_program"] = {
            k: seg_costs[k]
            for k in ("flops", "bytes_accessed", "temp_bytes")
            if k in seg_costs
        }
        if paged is None:
            # the dense backend's persistent allocation IS its ceiling
            results[mode]["kv_cache_bytes"] = int(st.kv_cache_bytes)
        else:
            results[mode].update(
                # the full pool allocation and the live-token high-water
                # are DIFFERENT numbers — report both so the artifact
                # cannot be misread (the pool is deliberately
                # over-provisioned; the high-water is the memory claim)
                pool_bytes_allocated=int(st.kv_cache_bytes),
                kv_bytes_high_water=int(st.kv_bytes_high_water),
                kv_blocks_in_use=int(st.kv_blocks_in_use),
                kv_blocks_total=int(st.kv_blocks_total),
                prefix_hit_rate=round(st.prefix_hit_rate, 4),
                prefix_tokens_saved=int(st.prefix_tokens_saved),
                decode_kernel=decode_kernel,
                # analytic bytes of the transient dense view the gather
                # decode materializes per segment (and the kernel deletes)
                gather_view_bytes_per_segment=(
                    dense_kv_bytes(tcfg, B, S) if decode_kernel == "xla" else 0
                ),
            )

    assert harvests["dense"] == harvests["paged"], (
        "paged harvest diverged from dense — bit-parity contract broken"
    )
    assert harvests["pallas"] == harvests["dense"], (
        "pallas kernel harvest diverged from dense — bit-parity broken"
    )
    results["bit_identical"] = True
    # claim (1): paged KV high-water (live tokens) vs the dense ceiling —
    # identical for both paged arms (same allocator trace)
    results["kv_high_water_vs_dense"] = round(
        results["paged"]["kv_bytes_high_water"]
        / max(results["dense"]["kv_cache_bytes"], 1),
        4,
    )
    # claim (2): prefill tokens saved by prefix-cache hits
    results["prefill_tokens_saved_frac"] = round(
        1.0
        - results["paged"]["prefill_tokens"]
        / max(results["dense"]["prefill_tokens"], 1),
        4,
    )
    results["speedup"] = round(
        results["dense"]["seconds"] / max(results["paged"]["seconds"], 1e-9), 3
    )
    results["speedup_pallas"] = round(
        results["dense"]["seconds"] / max(results["pallas"]["seconds"], 1e-9), 3
    )
    import jax as _jax

    results["backend"] = _jax.default_backend()
    results["provenance"] = provenance()
    if _jax.default_backend() != "tpu":
        results["pallas_note"] = (
            "off-TPU the pallas arm runs under the Pallas interpreter "
            "(kernel body as sequential per-row XLA ops): its wall-clock "
            "measures the interpreter, not the kernel — the committed "
            "claims at CPU scale are bit-parity through the real kernel "
            "code path and the decode_segment_program accounting (the "
            "gather arms carry a transient dense view per segment, the "
            "kernel arm carries none)"
        )
    return results


def measure_engine_prefill(
    policy_layers: int = 8,
    policy_hidden: int = 128,
    batch_size: int = 8,
    long_prompt_len: int = 96,
    short_prompt_len: int = 8,
    max_new_tokens: int = 48,
    n_long: int = 12,
    n_short: int = 36,
    absorb_frac: float = 0.1,
    kv_block_size: int = 8,
    segment_len: int = 8,
    prefill_chunk: int = 16,
    seed: int = _SEED,
) -> Dict[str, Any]:
    """Paged-prefill A/B (ISSUE 14; docs/PERFORMANCE.md "Pallas kernels" +
    "Chunked prefill") on a mixed long/short-prompt workload — the
    long-sequence failure mode PipelineRL (arXiv:2509.19128) identifies:
    a long prompt's monolithic refill stalls every live decode slot.

    Five arms over identical per-row RNG streams, harvest asserted
    bit-identical across ALL arms inside this function (so every delta is
    bookkeeping/scheduling, never a workload change):

    - ``dense``: the dense per-slot reference engine;
    - ``gather``: paged backend, monolithic gather-prefill-scatter refill
      (the PR-6 baseline) — reports the analytic refill gather/scatter
      bytes its programs move;
    - ``gather_chunked``: the same compiled-XLA prefill under
      chunked-prefill scheduling (``engine.prefill_chunk``) — claim (b)
      is measured HERE, compiled program against compiled program: long
      prompts prefill one chunk per step between decode segments and the
      measured ``decode_stall_max`` drops;
    - ``pallas``: ``engine.prefill_kernel: pallas`` — the in-place
      prefill kernel; claim (a): refill gather/scatter bytes exactly 0;
    - ``pallas_chunked``: both together, the full ISSUE-14 configuration.

    Off-TPU the pallas arms run under the Pallas interpreter: their
    wall-clock (and hence their interpreter-mode stall seconds, dominated
    by per-call interpreter overhead) measures the interpreter, not the
    kernel — which is why claim (b) is pinned on the compiled gather
    arms; on chip, ``python -m trlx_tpu.benchmark engine-prefill`` is the
    one-command wall-clock A/B across all five (ROADMAP item 1).
    """
    import numpy as np

    from trlx_tpu.trlx import initialize_runtime

    initialize_runtime()

    import jax
    import jax.numpy as jnp

    from trlx_tpu.data.configs import ModelConfig
    from trlx_tpu.engine.core import ContinuousEngine
    from trlx_tpu.models.builder import build_causal_lm
    from trlx_tpu.models.transformer import make_kv_cache
    from trlx_tpu.ops.paged_kv import PagedSpec
    from trlx_tpu.ops.sampling import (
        GenerationConfig,
        apply_transition_mask,
        per_row_keys,
    )
    from trlx_tpu.ops.slot_refill import make_slot_refill_fns
    from trlx_tpu.perf import lowered_costs

    # builtin:bytes vocab: ids 0..255 bytes, 256 bos, 257 eos, 258 pad
    vocab, eos, pad = 259, 257, 258
    absorb_n = max(1, int(absorb_frac * 256))
    trans = np.ones((vocab, vocab), bool)
    trans[:absorb_n, :] = False
    trans[:absorb_n, eos] = True
    tmask = jnp.asarray(trans)

    def adjust(step_out, logits):
        return apply_transition_mask(tmask, step_out["last_tokens"], logits)

    policy_extra = dict(
        num_layers=policy_layers,
        hidden_size=policy_hidden,
        num_heads=max(4, policy_hidden // 32),
        intermediate_size=4 * policy_hidden,
    )
    module, params, tcfg = build_causal_lm(
        ModelConfig(
            model_path="builtin:gpt2-test", model_extra_kwargs=dict(policy_extra)
        ),
        head="value",
    )

    def apply_fn(p, ids, **kw):
        return module.apply({"params": p}, ids, **kw)

    gen_config = GenerationConfig(
        max_new_tokens=max_new_tokens, eos_token_id=eos, pad_token_id=pad,
        do_sample=True, per_row_rng=True,
    )
    B, P, N = batch_size, long_prompt_len, max_new_tokens
    S = P + N
    rs = np.random.RandomState(seed)
    # mixed workload, interleaved so long prompts keep arriving while short
    # rows decode: every long prefill event stalls live slots on the
    # monolithic arms
    prompts = np.full((n_long + n_short, P), pad, np.int32)
    masks = np.zeros_like(prompts)
    order = rs.permutation(n_long + n_short)
    for j, is_long in enumerate(order < n_long):
        width = long_prompt_len if is_long else short_prompt_len
        prompts[j, P - width:] = rs.randint(0, 200, width)
        masks[j, P - width:] = 1
    n = prompts.shape[0]
    keys = np.asarray(per_row_keys(jax.random.PRNGKey(seed), n))

    TB = -(-S // kv_block_size)
    results: Dict[str, Any] = {
        "config": dict(
            policy=policy_extra, batch_size=B,
            long_prompt_len=long_prompt_len,
            short_prompt_len=short_prompt_len, max_new_tokens=N,
            n_long=n_long, n_short=n_short, absorb_frac=absorb_frac,
            kv_block_size=kv_block_size, segment_len=segment_len,
            prefill_chunk=prefill_chunk,
        )
    }

    harvests: Dict[str, Dict[int, Any]] = {}
    arms = (
        ("dense", None, None, 0),
        ("gather", "xla", "xla", 0),
        ("gather_chunked", "xla", "xla", prefill_chunk),
        ("pallas", "xla", "pallas", 0),
        ("pallas_chunked", "xla", "pallas", prefill_chunk),
    )
    for mode, decode_kernel, prefill_kernel, chunk in arms:
        paged = (
            PagedSpec(block_size=kv_block_size, max_blocks=1 + 2 * B * TB)
            if decode_kernel is not None
            else None
        )
        fns = make_slot_refill_fns(
            apply_fn, lambda b, s: make_kv_cache(tcfg, b, s), B, P, gen_config,
            adjust_logits=adjust, segment_len=segment_len,
            params_example=params, paged=paged,
            decode_kernel=decode_kernel or "xla",
            prefill_kernel=prefill_kernel or "xla",
        )
        engine = ContinuousEngine(
            fns, params, pad, prefill_chunk=chunk
        )

        def wave(ks, got):
            engine.enqueue_prompts(prompts, masks, ks)
            while engine.busy:
                for c in engine.step():
                    got[c.index % n] = (c.tokens.tobytes(), c.logprobs.tobytes())

        wave(keys, {})  # warmup: compiles refill/chunk buckets + segments
        engine.begin_collection(params)
        got: Dict[int, Any] = {}
        t0 = time.time()
        wave(keys, got)
        dt = time.time() - t0
        harvests[mode] = got
        st = engine.stats
        results[mode] = {
            "seconds": round(dt, 3),
            "rollout_tokens_per_sec": round(
                st.live_slot_steps / max(dt, 1e-9), 1
            ),
            "slot_utilization": round(st.slot_utilization, 4),
            "prefill_tokens": int(st.prefill_tokens),
            "refill_prefills": int(st.refill_prefills),
            # the decode-stall gauges (one sample per prefill event that
            # ran while live decode slots waited): the scheduling claim
            "decode_stall_events": len(st.decode_stall_samples),
            "decode_stall_p50_s": round(st.decode_stall_p50, 5),
            "decode_stall_p95_s": round(st.decode_stall_p95, 5),
            "decode_stall_max_s": round(st.decode_stall_max, 5),
            "decode_stall_total_s": round(st.decode_stall_s, 4),
        }
        if paged is not None:
            results[mode].update(
                prefill_kernel=prefill_kernel,
                prefill_chunk=chunk,
                prefill_chunk_calls=int(st.prefill_chunk_calls),
                # the acceptance number: the transient dense-view bytes
                # the refill prefills move — 0 under the in-place kernel
                refill_gather_bytes=int(st.refill_gather_bytes),
                refill_scatter_bytes=int(st.refill_scatter_bytes),
            )
            # XLA's compiled cost model for the full-bucket cold refill
            # program each paged arm runs — the program-level record of
            # the gather/scatter tax (present in the gather arm's refill,
            # absent from the kernel arms')
            TBs = engine.state.cache.block_table.shape[1]
            refill_costs = lowered_costs(
                fns.refill_program(B).lower(
                    params,
                    jax.eval_shape(fns.init_state),
                    jax.ShapeDtypeStruct((B, P), jnp.int32),
                    jax.ShapeDtypeStruct((B, P), jnp.int32),
                    jax.ShapeDtypeStruct((B,), jnp.int32),
                    jax.ShapeDtypeStruct((B, 2), jnp.uint32),
                    jax.ShapeDtypeStruct((B, TBs), jnp.int32),
                )
            )
            results[mode]["refill_program"] = {
                k: refill_costs[k]
                for k in ("flops", "bytes_accessed", "temp_bytes")
                if k in refill_costs
            }

    for mode in ("gather", "gather_chunked", "pallas", "pallas_chunked"):
        assert harvests[mode] == harvests["dense"], (
            f"{mode} harvest diverged from dense — bit-parity contract broken"
        )
    results["bit_identical"] = True
    # claim (a): the refill gather/scatter tax, deleted by the kernel —
    # measured on the chunked pair (the monolithic gather arm's COLD
    # refills take the zero-cache shortcut and only scatter; its chunked
    # twin gathers the committed prefix every span, which is the cost the
    # serving-shaped workload actually pays)
    results["refill_bytes_baseline"] = int(
        results["gather_chunked"]["refill_gather_bytes"]
        + results["gather_chunked"]["refill_scatter_bytes"]
    )
    for mode in ("pallas", "pallas_chunked"):
        assert results[mode]["refill_gather_bytes"] == 0
        assert results[mode]["refill_scatter_bytes"] == 0
    # claim (b): chunked scheduling bounds the decode stall — compiled-XLA
    # arm against compiled-XLA arm (the pallas arms' interpreter-mode
    # wall-clock is per-call-overhead-dominated off-TPU, see pallas_note)
    results["decode_stall_max_ratio"] = round(
        results["gather_chunked"]["decode_stall_max_s"]
        / max(results["gather"]["decode_stall_max_s"], 1e-9),
        4,
    )
    import jax as _jax

    results["backend"] = _jax.default_backend()
    results["provenance"] = provenance()
    if _jax.default_backend() != "tpu":
        results["pallas_note"] = (
            "off-TPU the pallas arms run under the Pallas interpreter "
            "(kernel body as sequential per-row XLA ops): their "
            "wall-clock and stall seconds measure per-call interpreter "
            "overhead, not the kernel — the committed CPU-scale claims "
            "are (a) bit-parity through the real kernel code path with "
            "analytic refill gather/scatter bytes = 0, and (b) the stall "
            "reduction on the compiled-XLA gather vs gather_chunked "
            "pair; the day a TPU window opens, this command is the "
            "wall-clock A/B across all five arms"
        )
    return results


def measure_engine_spec(
    policy_layers: int = 8,
    policy_hidden: int = 128,
    draft_layers: int = 2,
    draft_hidden: int = 64,
    batch_size: int = 8,
    prompt_len: int = 16,
    max_new_tokens: int = 48,
    num_rollouts: int = 16,
    gamma: int = 4,
    absorb_frac: float = 0.08,
    kv_block_size: int = 8,
    segment_len: int = 4,
    seed: int = _SEED,
) -> Dict[str, Any]:
    """Engine A/B: plain paged decode segments vs speculative decode
    segments (``engine.speculative = gamma``, docs/PERFORMANCE.md
    "Speculative continuous batching") on a heterogeneous-length workload
    — ``num_rollouts`` prompts drained through ``batch_size`` slots with
    an absorbing transition mask (geometric lengths → refill churn).

    The plain and spec arms run DIFFERENT per-row streams by construction
    (the spec sampler advances the per-row key chains gamma+2 draws per
    round, the plain sampler one per token), so the in-benchmark equality
    assert is the spec contract itself: each spec arm's harvest is
    bit-identical, per row, to one solo batched ``generate_speculative``
    call over all ``num_rollouts`` rows — refills, block tables, and
    batch composition invisible (the standing tier-1 pin:
    ``tests/test_spec_engine.py``). The third arm (``spec_pallas``) runs
    the same speculative rounds over the Pallas kernels — the in-place
    paged prefill plus the multi-position verify kernel
    (``ops/paged_attention.py::paged_verify_attention``) — and is held to
    the same solo reference, pinning that the kernel composition changes
    no bit of the harvest.

    The committed claims (benchmarks/ENGINE_SPEC_cpu.json):

    - ``bit_identical_tokens``: spec-engine tokens/mask ≡ solo speculative
      run bitwise, logprobs/values to ``float_drift_max`` ≤ 1 f32 ulp
      (the refill program's dead logits head shifts XLA fusion at these
      widths; tier-1 pins FULL bitwise equality where both programs lower
      identically — tests/test_spec_engine.py);
    - ``spec.acceptance_rate`` > 0 on a real (smaller, differently
      seeded) draft against the target;
    - ``target_forwards_per_token``: the speculation win in
      backend-independent units — the plain segment runs one target
      forward per committed token (1.0 by construction), the spec
      segment runs one VERIFY forward per round over gamma+1 positions,
      i.e. ``live_rounds / committed`` = 1/tokens_per_round < 1.0;
    - the verify-program cost analysis: XLA compiled flops/bytes of both
      arms' segment programs — the spec segment's flops per invocation
      buy up to ``segment_len × (gamma+1)`` tokens where the plain
      segment's buy ``segment_len``;
    - program accounting: speculation swaps the refill + segment program
      pair, it does not ADD programs per bucket (the perf-budget entry
      ``gpt2_test_spec`` pins the same claim structurally).
    """
    import numpy as np

    from trlx_tpu.trlx import initialize_runtime

    initialize_runtime()

    import jax
    import jax.numpy as jnp

    from trlx_tpu.data.configs import ModelConfig
    from trlx_tpu.engine.core import ContinuousEngine
    from trlx_tpu.models.builder import build_causal_lm
    from trlx_tpu.models.transformer import make_kv_cache
    from trlx_tpu.ops.paged_kv import PagedSpec
    from trlx_tpu.ops.sampling import (
        GenerationConfig,
        apply_transition_mask,
        per_row_keys,
    )
    from trlx_tpu.ops.slot_refill import make_slot_refill_fns
    from trlx_tpu.ops.speculative import generate_speculative
    from trlx_tpu.perf import lowered_costs

    # builtin:bytes vocab: ids 0..255 bytes, 256 bos, 257 eos, 258 pad (=259)
    vocab, eos, pad = 259, 257, 258
    absorb_n = max(1, int(absorb_frac * 256))
    trans = np.ones((vocab, vocab), bool)
    trans[:absorb_n, :] = False
    trans[:absorb_n, eos] = True
    tmask = jnp.asarray(trans)

    def adjust(step_out, logits):
        return apply_transition_mask(tmask, step_out["last_tokens"], logits)

    policy_extra = dict(
        num_layers=policy_layers,
        hidden_size=policy_hidden,
        num_heads=max(4, policy_hidden // 32),
        intermediate_size=4 * policy_hidden,
    )
    draft_extra = dict(
        num_layers=draft_layers,
        hidden_size=draft_hidden,
        num_heads=max(4, draft_hidden // 32),
        intermediate_size=4 * draft_hidden,
    )
    # f32 compute: the bit-parity contract is pinned at f32 (same as the
    # tier-1 tests) — bf16 compute drifts at ulp scale between the
    # engine's and the solo sampler's lowerings (tokens unaffected; the
    # logprob bits differ), so a parity-ASSERTING artifact must not run it
    f32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
    t_mod, t_params, tcfg = build_causal_lm(
        ModelConfig(
            model_path="builtin:gpt2-test",
            model_extra_kwargs=dict(policy_extra, **f32),
        ),
        head="value",
    )
    d_mod, d_params, dcfg = build_causal_lm(
        ModelConfig(
            model_path="builtin:gpt2-test",
            model_extra_kwargs=dict(draft_extra, **f32),
        ),
        head=None,
        seed=seed + 1,
    )

    def t_apply(p, ids, **kw):
        return t_mod.apply({"params": p}, ids, **kw)

    def d_apply(p, ids, **kw):
        return d_mod.apply({"params": p}, ids, **kw)

    gen_config = GenerationConfig(
        max_new_tokens=max_new_tokens, eos_token_id=eos, pad_token_id=pad,
        do_sample=True, per_row_rng=True,
    )
    B, P, N, G = batch_size, prompt_len, max_new_tokens, gamma
    rs = np.random.RandomState(seed)
    prompts = rs.randint(0, 200, (num_rollouts, P)).astype(np.int32)
    masks = np.ones_like(prompts)
    key_rng = jax.random.PRNGKey(seed)
    warm_key, run_key = jax.random.split(key_rng)
    warm_keys = np.asarray(per_row_keys(warm_key, num_rollouts))
    run_keys = np.asarray(per_row_keys(run_key, num_rollouts))

    results: Dict[str, Any] = {
        "config": dict(
            policy=policy_extra, draft=draft_extra, batch_size=B,
            prompt_len=P, max_new_tokens=N, num_rollouts=num_rollouts,
            gamma=G, absorb_frac=absorb_frac,
            kv_block_size=kv_block_size, segment_len=segment_len,
            compute_dtype="float32",
        )
    }

    harvests: Dict[str, Dict[int, Any]] = {}
    # three arms: the plain paged segments, the speculative segments over
    # the gather-reference kernels, and the speculative segments over the
    # Pallas kernels (decode_kernel + prefill_kernel: pallas — the spec
    # refill commits prompt K/V through the block table in place and the
    # verify forward runs the multi-position paged kernel,
    # ops/paged_attention.py::paged_verify_attention). Both spec arms
    # decode the SAME per-row streams, so both are parity-asserted against
    # the one solo run below.
    for mode in ("plain", "spec", "spec_pallas"):
        g = 0 if mode == "plain" else G
        S = P + N + g
        TB = -(-S // kv_block_size)
        paged = PagedSpec(block_size=kv_block_size, max_blocks=1 + 2 * B * TB)
        spec_kwargs = (
            dict(
                speculative=G, draft_apply=d_apply,
                init_draft_cache_fn=lambda b, s: make_kv_cache(dcfg, b, s),
                transition_mask=tmask,
            )
            if mode != "plain"
            # the plain arm composes the mask into adjust (the non-spec
            # convention); the spec arms pass it separately so draft AND
            # target are constrained inside the shared round
            else dict(adjust_logits=adjust)
        )
        if mode == "spec_pallas":
            spec_kwargs.update(decode_kernel="pallas", prefill_kernel="pallas")
        fns = make_slot_refill_fns(
            t_apply, lambda b, s: make_kv_cache(tcfg, b, s), B, P, gen_config,
            segment_len=segment_len, params_example=t_params, paged=paged,
            **spec_kwargs,
        )
        eng_params = t_params if mode == "plain" else (t_params, d_params)
        engine = ContinuousEngine(fns, eng_params, pad, prefix_cache=True)

        def wave(keys, got):
            engine.enqueue_prompts(prompts, masks, keys)
            while engine.busy:
                for c in engine.step():
                    # request indices run on across waves; fold back to
                    # the row number within this wave's enqueue order
                    got[c.index % num_rollouts] = {
                        "tokens": np.asarray(c.tokens),
                        "logprobs": np.asarray(c.logprobs),
                        "values": np.asarray(c.values),
                        "mask": np.asarray(c.mask),
                    }

        wave(warm_keys, {})  # warmup: compiles the refill buckets + segment
        engine.begin_collection(eng_params)
        got: Dict[int, Any] = {}
        t0 = time.time()
        wave(run_keys, got)
        dt = time.time() - t0
        harvests[mode] = got
        st = engine.stats
        m = st.metrics()
        results[mode] = {
            "seconds": round(dt, 3),
            "rollout_tokens_per_sec": round(
                st.live_slot_steps / max(dt, 1e-9), 1
            ),
            "slot_utilization": round(st.slot_utilization, 4),
            "prefill_tokens": int(st.prefill_tokens),
            "segment_program": {
                k: v
                for k, v in lowered_costs(
                    fns.decode_segment.lower(eng_params, engine.state)
                ).items()
                if k in ("flops", "bytes_accessed", "temp_bytes")
            },
        }
        if mode != "plain":
            results[mode].update(
                acceptance_rate=round(m["engine/spec_acceptance_rate"], 4),
                tokens_per_round=round(m["engine/spec_tokens_per_round"], 4),
                spec_rounds=int(m["rollout/spec_rounds"]),
                # verify forwards per committed token — the speculation
                # win in backend-independent units (plain = 1.0)
                target_forwards_per_token=round(
                    st.spec_live_rounds / max(st.spec_committed, 1), 4
                ),
                # which verify compute ran: the multi-position Pallas
                # paged kernel (in place) or the gather-reference shape
                verify_kernel=(
                    "pallas" if mode == "spec_pallas" else "xla"
                ),
            )

    # the in-benchmark bit-parity assert: the spec engine's harvest must
    # equal ONE solo batched speculative run of the same rows/keys — the
    # paged plumbing (refills, block tables, neighbors) is invisible
    solo = generate_speculative(
        t_apply, t_params, d_apply, d_params,
        lambda b, s: make_kv_cache(tcfg, b, s),
        lambda b, s: make_kv_cache(dcfg, b, s),
        jnp.asarray(prompts), jnp.asarray(masks), jnp.asarray(run_keys),
        gen_config, gamma=G, transition_mask=tmask,
    )
    float_drift = 0.0
    for arm in ("spec", "spec_pallas"):
        for i in range(num_rollouts):
            for field, solo_arr in (
                ("tokens", solo.response_tokens),
                ("mask", solo.response_mask),
            ):
                assert (
                    harvests[arm][i][field] == np.asarray(solo_arr)[i]
                ).all(), (
                    f"{arm} engine harvest diverged from solo speculative "
                    f"run (row {i}, {field}) — bit-parity contract broken"
                )
            for field, solo_arr in (
                ("logprobs", solo.response_logprobs),
                ("values", solo.response_values),
            ):
                d = float(
                    np.abs(harvests[arm][i][field] - np.asarray(solo_arr)[i]).max()
                )
                float_drift = max(float_drift, d)
                assert d <= 4e-6, (
                    f"{arm} engine {field} diverged from solo beyond ulp "
                    f"scale (row {i}, max {d:.3e}) — parity contract broken"
                )
    results["bit_identical_tokens"] = True
    # logprobs/values agree to ≤1 f32 ulp at these widths: the refill
    # program compiles separately from the solo sampler (its logits head
    # is dead code, which shifts XLA's last-layer fusion), so committed
    # prompt K/V can carry 1-ulp drift. The tier-1 tests pin FULL bitwise
    # equality — logprobs and values included — at the width where both
    # programs lower identically (tests/test_spec_engine.py); the round
    # function itself is shared code, not a reimplementation.
    results["float_drift_max"] = float_drift
    assert results["spec"]["acceptance_rate"] > 0.0, (
        "zero acceptance on a real draft/target pair"
    )
    # the pallas arm replays the same streams, so its acceptance matches
    assert (
        results["spec_pallas"]["acceptance_rate"]
        == results["spec"]["acceptance_rate"]
    ), "pallas verify kernel changed the acceptance trace"
    results["speedup"] = round(
        results["plain"]["seconds"] / max(results["spec"]["seconds"], 1e-9), 3
    )
    results["programs_note"] = (
        "speculation SWAPS the per-bucket program pair (refill, segment) "
        "for (spec refill, spec segment) — it adds zero programs per "
        "bucket; perf budgets gpt2_test_spec and gpt2_test_spec_kernel "
        "(benchmarks/perf_budgets.json) pin both programs' compiled costs "
        "for the gather-reference and Pallas-kernel compositions"
    )
    import jax as _jax

    results["backend"] = _jax.default_backend()
    results["provenance"] = provenance()
    if _jax.default_backend() != "tpu":
        results["cpu_note"] = (
            "CPU-scale run: per-segment dispatch overhead dominates the "
            "tiny models, so wall-clock speedup is NOT the claim — the "
            "committed claims are (a) parity of the spec engine harvest "
            "against the solo speculative sampler (tokens/mask bitwise, "
            "logprobs/values to float_drift_max ≤ 1 f32 ulp — see the "
            "bit_identical_tokens comment; tier-1 pins full bitwise "
            "equality), (b) acceptance "
            "> 0 on a real draft/target pair, and (c) "
            "target_forwards_per_token < 1.0 with the segment-program "
            "cost analysis: the verify forward's cost is amortized over "
            "tokens_per_round committed tokens. The spec_pallas arm runs "
            "the same rounds with the multi-position Pallas verify kernel "
            "+ in-place prefill — off-TPU under the Pallas interpreter, "
            "so its wall-clock measures the interpreter, not the kernel; "
            "its committed claim is bit-parity (same solo reference, same "
            "acceptance trace) through the real kernel code path. On "
            "chip, run: "
            "TRLX_TPU_PLATFORM=tpu python -m trlx_tpu.benchmark "
            "engine-spec --policy-layers 24 --policy-hidden 1024 "
            "--draft-layers 4 --draft-hidden 256 --batch-size 64 "
            "--max-new-tokens 256 --num-rollouts 512"
        )
    return results


def measure_loss_kernel(
    batch_size: int = 64,
    response_len: int = 128,
    block_rows: int = 8,
    rounds: int = 20,
    seed: int = _SEED,
) -> Dict[str, Any]:
    """Learner-step A/B: the staged XLA loss chain vs the fused Pallas
    kernel (``method.loss_kernel: pallas``, ops/fused_loss.py;
    docs/PERFORMANCE.md "Fused learner kernels") on a synthetic PPO batch
    of ``[batch_size, response_len]`` response windows with geometric
    per-row lengths.

    Three program measurements, all from XLA's compiled cost model
    (``trlx_tpu/perf.py::lowered_costs``) over identical runtime operands:

    - ``staged``: the three learner stages compiled as SEPARATE programs
      — GAE (``get_advantages_and_returns`` without whitening), masked
      whitening (``utils/stats.py::whiten``), and the clipped losses +
      stats (``PPOConfig.loss``) — so every ``[B, R]`` intermediate
      (advantages, returns, whitened advantages) crosses a program
      boundary through HBM. This is the per-stage round-trip accounting
      the fusion deletes;
    - ``xla``: the trainer's actual reference path
      (``fused_ppo_loss_reference``) in ONE jit — XLA already fuses what
      it can across the stages, but the GAE scan and the whitening
      reductions still materialize their ``[B, R]`` outputs;
    - ``fused``: the fused Pallas program (``fused_ppo_loss``) — each
      operand enters VMEM once, advantages/returns/whitening live and die
      on-chip.

    Both loss-and-stats and gradient (``d loss / d (logprobs, values)``)
    programs are measured, and the fused path is asserted BIT-IDENTICAL
    to the XLA reference in-function — loss, every stat, both grads —
    before any number is reported (jit-to-jit, every operand a runtime
    argument; see tests/test_fused_loss.py for why that harness rule
    matters). The committed acceptance number is the bytes-accessed
    reduction of ``fused`` against ``staged`` (and against ``xla``),
    plus the analytic inter-stage ``[B, R]`` round-trip bytes the fusion
    removes. Off-TPU the fused program runs under the Pallas interpreter,
    so its wall-clock measures the interpreter, not the kernel — see
    ``pallas_note`` in the artifact.
    """
    import numpy as np

    from trlx_tpu.trlx import initialize_runtime

    initialize_runtime()

    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.ppo import PPOConfig
    from trlx_tpu.ops.fused_loss import fused_ppo_loss, fused_ppo_loss_reference
    from trlx_tpu.perf import lowered_costs
    from trlx_tpu.utils.stats import whiten

    B, R = batch_size, response_len
    rs = np.random.RandomState(seed)
    # geometric per-row response lengths in [1, R]: the heterogeneous mask
    # shape the whitening/GAE epilogue sees in real collection
    lengths = np.clip(rs.geometric(p=4.0 / R, size=B), 1, R)
    mask = np.zeros((B, R), np.float32)
    for i, n in enumerate(lengths):
        mask[i, :n] = 1.0
    ops = (
        jnp.asarray(rs.randn(B, R).astype(np.float32) * 0.1),  # logprobs
        jnp.asarray(rs.randn(B, R).astype(np.float32)),  # values
        jnp.asarray(rs.randn(B, R).astype(np.float32) * 0.1),  # old_logprobs
        jnp.asarray(rs.randn(B, R).astype(np.float32)),  # old_values
        jnp.asarray(rs.randn(B, R).astype(np.float32) * 0.05),  # rewards
        jnp.asarray(mask),
    )
    method = PPOConfig(name="PPOConfig")

    def ref(*a):
        return fused_ppo_loss_reference(method, *a)

    def fus(*a):
        return fused_ppo_loss(method, *a, block_rows=block_rows)

    # the staged chain as three separately-compiled programs: the [B, R]
    # intermediates (advantages, returns, whitened advantages) cross HBM
    # at every boundary — the accounting the fused program deletes
    def stage_gae(old_values, rewards, m):
        return method.get_advantages_and_returns(
            old_values, rewards, m, use_whitening=False
        )

    def stage_whiten(advantages, m):
        return whiten(advantages, m)

    def stage_loss(logprobs, values, old_logprobs, old_values, adv, ret, m):
        return method.loss(
            logprobs=logprobs, values=values, old_logprobs=old_logprobs,
            old_values=old_values, advantages=adv, returns=ret, mask=m,
        )

    lp, v, olp, ov, rw, m = ops
    adv_raw, ret = jax.jit(stage_gae)(ov, rw, m)
    adv = jax.jit(stage_whiten)(adv_raw, m)

    def costs(lowered):
        c = lowered_costs(lowered)
        return {
            k: c[k]
            for k in ("flops", "bytes_accessed", "temp_bytes")
            if k in c
        }

    staged_stages = {
        "gae": costs(jax.jit(stage_gae).lower(ov, rw, m)),
        "whiten": costs(jax.jit(stage_whiten).lower(adv_raw, m)),
        "loss": costs(jax.jit(stage_loss).lower(lp, v, olp, ov, adv, ret, m)),
    }
    staged_total = {
        k: sum(s[k] for s in staged_stages.values() if k in s)
        for k in ("flops", "bytes_accessed", "temp_bytes")
    }

    def grad_fn(fn):
        return jax.jit(jax.grad(lambda *a: fn(*a)[0], argnums=(0, 1)))

    programs = {
        "staged": {"stages": staged_stages, "total": staged_total},
        "xla": {
            "loss": costs(jax.jit(ref).lower(*ops)),
            "loss_grad": costs(grad_fn(ref).lower(*ops)),
        },
        "fused": {
            "loss": costs(jax.jit(fus).lower(*ops)),
            "loss_grad": costs(grad_fn(fus).lower(*ops)),
        },
    }

    # bit-parity gate: no cost number is reported unless the fused program
    # is bit-identical to the reference on these exact operands
    rl, rstats = jax.jit(ref)(*ops)
    fl, fstats = jax.jit(fus)(*ops)
    assert jnp.array_equal(rl, fl), "fused loss != xla loss — parity broken"
    assert set(rstats) == set(fstats)
    for k in rstats:
        assert jnp.array_equal(rstats[k], fstats[k]), (
            f"fused stat {k} != xla — parity broken"
        )
    gr = grad_fn(ref)(*ops)
    gf = grad_fn(fus)(*ops)
    assert jnp.array_equal(gr[0], gf[0]) and jnp.array_equal(gr[1], gf[1]), (
        "fused grads != xla grads — parity broken"
    )

    # interpret-mode-caveated wall clock (meaningful on chip only)
    timings = {}
    for name, fn in (("xla", grad_fn(ref)), ("fused", grad_fn(fus))):
        jax.block_until_ready(fn(*ops))  # warmup/compile
        t0 = time.time()
        for _ in range(rounds):
            out = fn(*ops)
        jax.block_until_ready(out)
        timings[name] = round((time.time() - t0) / rounds, 6)

    f32 = 4
    results: Dict[str, Any] = {
        "config": dict(
            batch_size=B, response_len=R, block_rows=block_rows,
            rounds=rounds, seed=seed,
            response_len_mean=round(float(lengths.mean()), 2),
        ),
        "bit_identical": True,
        "programs": programs,
        # the acceptance numbers: one fused program instead of per-stage
        # [B, R] HBM round-trips
        "bytes_accessed_reduction_vs_staged": round(
            1.0
            - programs["fused"]["loss"]["bytes_accessed"]
            / max(staged_total["bytes_accessed"], 1.0),
            4,
        ),
        "bytes_accessed_reduction_vs_xla": round(
            1.0
            - programs["fused"]["loss"]["bytes_accessed"]
            / max(programs["xla"]["loss"]["bytes_accessed"], 1.0),
            4,
        ),
        # the [B, R] intermediates that cross program boundaries in the
        # staged chain (advantages, returns, whitened advantages — each
        # written by one stage and read by the next): exact arithmetic,
        # backend-independent
        "analytic_interstage_bytes": int(3 * 2 * B * R * f32),
        "accounting_note": (
            "the staged entry is the per-stage dispatch accounting "
            "(three programs, intermediates through HBM) — the round-trips "
            "the fusion deletes; the xla entry is the same chain in one "
            "jit, where the CPU cost model already credits XLA's own "
            "fusion, so fused-vs-xla measures interpret-lowering overhead "
            "(0 here: the fused program compiles to the identical cost) "
            "and the VMEM-residency win is an on-chip property the CPU "
            "cost model cannot see"
        ),
        "loss_grad_seconds_per_call": timings,
    }
    import jax as _jax

    results["backend"] = _jax.default_backend()
    results["provenance"] = provenance()
    if _jax.default_backend() != "tpu":
        results["pallas_note"] = (
            "off-TPU the fused program runs under the Pallas interpreter "
            "(kernel body as sequential XLA ops): its wall-clock and its "
            "own cost-analysis numbers measure the interpreter lowering, "
            "not the Mosaic kernel — the committed CPU-scale claims are "
            "bit-parity (loss/stats/grads, asserted in-function) through "
            "the real kernel code path and the staged-chain bytes-accessed "
            "accounting (three separately-compiled stages round-trip the "
            "[B, R] intermediates through HBM; the fused path is one "
            "program). On chip, run: TRLX_TPU_PLATFORM=tpu python -m "
            "trlx_tpu.benchmark loss-kernel --batch-size 128 "
            "--response-len 512"
        )
    return results


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = parser.add_subparsers(dest="cmd", required=True)
    run_p = sub.add_parser("run", help="run the benchmark suite")
    run_p.add_argument("--output-dir", required=True)
    run_p.add_argument("--tasks", nargs="*", default=None, choices=sorted(TASKS))
    run_p.add_argument("--scale", choices=("ci", "full"), default="ci")
    rep_p = sub.add_parser("report", help="compare two suite runs")
    rep_p.add_argument("run_a")
    rep_p.add_argument("run_b")
    rep_p.add_argument("--output", default=None, help="write markdown here (default stdout)")
    spec_p = sub.add_parser(
        "speculative", help="A/B rollout throughput: plain vs speculative decoding"
    )
    spec_p.add_argument("--output", default=None, help="write JSON here (default stdout)")
    spec_p.add_argument("--policy-layers", type=int, default=24)
    spec_p.add_argument("--policy-hidden", type=int, default=256)
    spec_p.add_argument("--gamma", type=int, default=4)
    spec_p.add_argument("--rounds", type=int, default=8)
    cb_p = sub.add_parser(
        "continuous-batching",
        help="A/B rollout collection: serial chunked decode vs slot-refill "
        "continuous batching on a heterogeneous-length workload",
    )
    cb_p.add_argument("--output", default=None, help="write JSON here (default stdout)")
    cb_p.add_argument("--policy-layers", type=int, default=8)
    cb_p.add_argument("--policy-hidden", type=int, default=128)
    cb_p.add_argument("--batch-size", type=int, default=16)
    cb_p.add_argument("--max-new-tokens", type=int, default=96)
    cb_p.add_argument("--num-rollouts", type=int, default=64)
    cb_p.add_argument("--absorb-frac", type=float, default=0.08)
    cb_p.add_argument("--segment-len", type=int, default=8)
    cb_p.add_argument("--rounds", type=int, default=3)
    ep_p = sub.add_parser(
        "engine-paged",
        help="A/B generation engine: dense per-slot KV vs paged block-pool "
        "KV + prefix cache on a shared-prefix (GRPO-group/eval) workload",
    )
    ep_p.add_argument("--output", default=None, help="write JSON here (default stdout)")
    ep_p.add_argument("--policy-layers", type=int, default=8)
    ep_p.add_argument("--policy-hidden", type=int, default=128)
    ep_p.add_argument("--batch-size", type=int, default=16)
    ep_p.add_argument("--prompt-len", type=int, default=32)
    ep_p.add_argument("--max-new-tokens", type=int, default=96)
    ep_p.add_argument("--group-size", type=int, default=8)
    ep_p.add_argument("--n-groups", type=int, default=8)
    ep_p.add_argument("--passes", type=int, default=2)
    ep_p.add_argument("--absorb-frac", type=float, default=0.08)
    ep_p.add_argument("--kv-block-size", type=int, default=8)
    ep_p.add_argument("--segment-len", type=int, default=8)
    es_p = sub.add_parser(
        "engine-spec",
        help="A/B generation engine: plain paged decode segments vs "
        "speculative (draft-propose + single-forward verify) decode "
        "segments on a heterogeneous-length workload",
    )
    es_p.add_argument("--output", default=None, help="write JSON here (default stdout)")
    es_p.add_argument("--policy-layers", type=int, default=8)
    es_p.add_argument("--policy-hidden", type=int, default=128)
    es_p.add_argument("--draft-layers", type=int, default=2)
    es_p.add_argument("--draft-hidden", type=int, default=64)
    es_p.add_argument("--batch-size", type=int, default=8)
    es_p.add_argument("--prompt-len", type=int, default=16)
    es_p.add_argument("--max-new-tokens", type=int, default=48)
    es_p.add_argument("--num-rollouts", type=int, default=16)
    es_p.add_argument("--gamma", type=int, default=4)
    es_p.add_argument("--absorb-frac", type=float, default=0.08)
    es_p.add_argument("--kv-block-size", type=int, default=8)
    es_p.add_argument("--segment-len", type=int, default=4)
    lk_p = sub.add_parser(
        "loss-kernel",
        help="A/B learner step: staged XLA GAE/whitening/loss chain vs "
        "the fused Pallas kernel (method.loss_kernel: pallas) — "
        "bit-parity asserted, compiled bytes-accessed recorded",
    )
    lk_p.add_argument("--output", default=None, help="write JSON here (default stdout)")
    lk_p.add_argument("--batch-size", type=int, default=64)
    lk_p.add_argument("--response-len", type=int, default=128)
    lk_p.add_argument("--block-rows", type=int, default=8)
    lk_p.add_argument("--rounds", type=int, default=20)
    pf_p = sub.add_parser(
        "engine-prefill",
        help="A/B paged prefill: gather-prefill-scatter vs the in-place "
        "Pallas prefill kernel + chunked-prefill scheduling on a mixed "
        "long/short-prompt workload",
    )
    pf_p.add_argument("--output", default=None, help="write JSON here (default stdout)")
    pf_p.add_argument("--policy-layers", type=int, default=8)
    pf_p.add_argument("--policy-hidden", type=int, default=128)
    pf_p.add_argument("--batch-size", type=int, default=8)
    pf_p.add_argument("--long-prompt-len", type=int, default=96)
    pf_p.add_argument("--short-prompt-len", type=int, default=8)
    pf_p.add_argument("--max-new-tokens", type=int, default=48)
    pf_p.add_argument("--n-long", type=int, default=12)
    pf_p.add_argument("--n-short", type=int, default=36)
    pf_p.add_argument("--absorb-frac", type=float, default=0.1)
    pf_p.add_argument("--kv-block-size", type=int, default=8)
    pf_p.add_argument("--segment-len", type=int, default=8)
    pf_p.add_argument("--prefill-chunk", type=int, default=16)
    args = parser.parse_args(argv)

    if args.cmd == "run":
        records = run_suite(args.output_dir, tasks=args.tasks, scale=args.scale)
        return 0 if all(r["rc"] == 0 for r in records) else 1
    if args.cmd == "speculative":
        result = measure_speculative(
            policy_layers=args.policy_layers,
            policy_hidden=args.policy_hidden,
            gamma=args.gamma,
            rounds=args.rounds,
        )
        text = json.dumps(result, indent=2)
        if args.output:
            with open(args.output, "w") as f:
                f.write(text + "\n")
        print(text)
        return 0
    if args.cmd == "continuous-batching":
        result = measure_continuous_batching(
            policy_layers=args.policy_layers,
            policy_hidden=args.policy_hidden,
            batch_size=args.batch_size,
            max_new_tokens=args.max_new_tokens,
            num_rollouts=args.num_rollouts,
            absorb_frac=args.absorb_frac,
            segment_len=args.segment_len,
            rounds=args.rounds,
        )
        text = json.dumps(result, indent=2)
        if args.output:
            with open(args.output, "w") as f:
                f.write(text + "\n")
        print(text)
        return 0
    if args.cmd == "engine-paged":
        result = measure_engine_paged(
            policy_layers=args.policy_layers,
            policy_hidden=args.policy_hidden,
            batch_size=args.batch_size,
            prompt_len=args.prompt_len,
            max_new_tokens=args.max_new_tokens,
            group_size=args.group_size,
            n_groups=args.n_groups,
            passes=args.passes,
            absorb_frac=args.absorb_frac,
            kv_block_size=args.kv_block_size,
            segment_len=args.segment_len,
        )
        text = json.dumps(result, indent=2)
        if args.output:
            with open(args.output, "w") as f:
                f.write(text + "\n")
        print(text)
        return 0
    if args.cmd == "engine-spec":
        result = measure_engine_spec(
            policy_layers=args.policy_layers,
            policy_hidden=args.policy_hidden,
            draft_layers=args.draft_layers,
            draft_hidden=args.draft_hidden,
            batch_size=args.batch_size,
            prompt_len=args.prompt_len,
            max_new_tokens=args.max_new_tokens,
            num_rollouts=args.num_rollouts,
            gamma=args.gamma,
            absorb_frac=args.absorb_frac,
            kv_block_size=args.kv_block_size,
            segment_len=args.segment_len,
        )
        text = json.dumps(result, indent=2)
        if args.output:
            with open(args.output, "w") as f:
                f.write(text + "\n")
        print(text)
        return 0
    if args.cmd == "loss-kernel":
        result = measure_loss_kernel(
            batch_size=args.batch_size,
            response_len=args.response_len,
            block_rows=args.block_rows,
            rounds=args.rounds,
        )
        text = json.dumps(result, indent=2)
        if args.output:
            with open(args.output, "w") as f:
                f.write(text + "\n")
        print(text)
        return 0
    if args.cmd == "engine-prefill":
        result = measure_engine_prefill(
            policy_layers=args.policy_layers,
            policy_hidden=args.policy_hidden,
            batch_size=args.batch_size,
            long_prompt_len=args.long_prompt_len,
            short_prompt_len=args.short_prompt_len,
            max_new_tokens=args.max_new_tokens,
            n_long=args.n_long,
            n_short=args.n_short,
            absorb_frac=args.absorb_frac,
            kv_block_size=args.kv_block_size,
            segment_len=args.segment_len,
            prefill_chunk=args.prefill_chunk,
        )
        text = json.dumps(result, indent=2)
        if args.output:
            with open(args.output, "w") as f:
                f.write(text + "\n")
        print(text)
        return 0
    text = compare_runs(args.run_a, args.run_b)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
