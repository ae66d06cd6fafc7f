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
    args = parser.parse_args(argv)

    if args.cmd == "run":
        records = run_suite(args.output_dir, tasks=args.tasks, scale=args.scale)
        return 0 if all(r["rc"] == 0 for r in records) else 1
    text = compare_runs(args.run_a, args.run_b)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
