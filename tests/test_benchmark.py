"""Benchmark suite + comparator tests (VERDICT #7 done-criterion: a two-run
comparison report generated at CI size). Reference surface:
``scripts/benchmark.sh`` + ``trlx/reference.py``.
"""

import json
import os

import pytest

from trlx_tpu.benchmark import TASKS, compare_runs, run_suite

CPU_ENV = {
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "TRLX_TPU_PLATFORM": "cpu",
    "TRLX_TPU_NO_TQDM": "1",
    "JAX_COMPILATION_CACHE_DIR": "/tmp/jax_test_cache",
}


def test_task_table_covers_benchmark_sh_suite():
    # the reference suite: randomwalks anchors + the sentiment quartet
    assert {"ppo_randomwalks", "ilql_randomwalks", "ppo_sentiments",
            "ilql_sentiments", "sft_sentiments", "ppo_sentiments_t5",
            "grpo_sentiments", "dpo_sentiments", "grpo_moe_mixtral",
            "ppo_speculative"} <= set(TASKS)
    for name, (script, _) in TASKS.items():
        assert os.path.exists(script), script


@pytest.mark.slow
def test_two_run_comparison_report(tmp_path):
    run_a, run_b = str(tmp_path / "a"), str(tmp_path / "b")
    for run in (run_a, run_b):
        records = run_suite(
            run, tasks=["ppo_randomwalks"], scale="ci", extra_env=CPU_ENV, timeout=1200
        )
        assert all(r["rc"] == 0 for r in records), records
        assert os.path.exists(os.path.join(run, "ppo_randomwalks", "stats.jsonl"))
        meta = json.load(open(os.path.join(run, "meta.json")))
        assert meta["scale"] == "ci" and meta["tasks"][0]["task"] == "ppo_randomwalks"

    report = compare_runs(run_a, run_b)
    assert "| ppo_randomwalks |" in report
    # at least one metric row with finite A/B values and a delta column
    rows = [l for l in report.splitlines() if l.startswith("| ppo_randomwalks |")]
    assert rows and all(len(r.split("|")) == 9 for r in rows)
