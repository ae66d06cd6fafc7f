"""Sweep runner tests (reference surface: ``trlx/sweep.py``): param-space
sampling correctness, grid × sample composition, and a real 2-param sweep
over randomwalks PPO at CI size (subprocess trials on the virtual CPU mesh).
"""

import json
import os
import sys
import textwrap

import numpy as np
import pytest

from trlx_tpu.sweep import ParamDef, SweepSpace, run_sweep


def test_param_strategies():
    rng = np.random.RandomState(0)
    assert 1e-6 <= ParamDef("lr", "loguniform", [1e-6, 1e-3]).sample(0.5, rng) <= 1e-3
    assert ParamDef("x", "uniform", [2.0, 4.0]).sample(0.5, rng) == 3.0
    assert ParamDef("x", "quniform", [0.0, 1.0, 0.25]).sample(0.37, rng) in (0.25, 0.5)
    assert ParamDef("k", "choice", [1, 5, 10]).sample(0.0, rng) in (1, 5, 10)
    assert isinstance(ParamDef("n", "randint", [1, 9]).sample(0.99, rng), int)
    with pytest.raises(ValueError, match="Unknown strategy"):
        ParamDef("x", "bogus", []).sample(0.5, rng)


def test_space_grid_times_samples():
    space = SweepSpace.from_config(
        {
            "tune_config": {"num_samples": 3},
            "optimizer.kwargs.lr": {"strategy": "loguniform", "values": [1e-5, 1e-3]},
            "method.ppo_epochs": {"strategy": "grid", "values": [2, 4]},
        }
    )
    trials = list(space.trials(3, seed=1))
    assert len(trials) == 6  # 3 samples × 2 grid points
    assert {t["method.ppo_epochs"] for t in trials} == {2, 4}
    assert all(1e-5 <= t["optimizer.kwargs.lr"] <= 1e-3 for t in trials)


def test_quasirandom_coverage():
    space = SweepSpace.from_config(
        {"x": {"strategy": "uniform", "values": [0.0, 1.0]}}
    )
    xs = [t["x"] for t in space.trials(8, search_alg="quasirandom")]
    # Halton base-2: evenly stratified — every quarter of [0,1] hit
    hist, _ = np.histogram(xs, bins=4, range=(0, 1))
    assert (hist > 0).all()


def test_sweep_randomwalks_ppo(tmp_path):
    """VERDICT #6 done-criterion: sweep 2 params over randomwalks PPO on the
    CPU mesh; every trial reports a finite metric and the report ranks them."""
    script = os.path.join(
        os.path.dirname(__file__), "..", "examples", "randomwalks", "ppo_randomwalks.py"
    )
    config = {
        "tune_config": {
            "mode": "max",
            "metric": "metrics/optimality",
            "search_alg": "random",
            "num_samples": 2,
        },
        "optimizer.kwargs.lr": {"strategy": "loguniform", "values": [1e-4, 1e-3]},
        "method.init_kl_coef": {"strategy": "uniform", "values": [0.0, 0.1]},
        # shrink to CI size
        "train.total_steps": {"strategy": "grid", "values": [2]},
        "train.batch_size": {"strategy": "grid", "values": [8]},
        "train.eval_interval": {"strategy": "grid", "values": [2]},
        "train.checkpoint_interval": {"strategy": "grid", "values": [1000]},
        "train.save_best": {"strategy": "grid", "values": [False]},
        "method.num_rollouts": {"strategy": "grid", "values": [8]},
        "method.chunk_size": {"strategy": "grid", "values": [8]},
        "method.ppo_epochs": {"strategy": "grid", "values": [1]},
    }
    records = run_sweep(
        script,
        config,
        str(tmp_path / "sweep_out"),
        trial_timeout=1200,
        extra_env={
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "TRLX_TPU_PLATFORM": "cpu",
            "TRLX_TPU_NO_TQDM": "1",
            "JAX_COMPILATION_CACHE_DIR": "/tmp/jax_test_cache",
        },
    )
    assert len(records) == 2
    for r in records:
        assert r["rc"] == 0, open(str(tmp_path / "sweep_out" / f"trial_{r['trial']:03d}.log")).read()[-2000:]
        assert r["metric"] is not None and np.isfinite(r["metric"])
        assert set(r["hparams"]) >= {"optimizer.kwargs.lr", "method.init_kl_coef"}
    assert os.path.exists(tmp_path / "sweep_out" / "results.jsonl")
    report = open(tmp_path / "sweep_out" / "report.md").read()
    assert "Best: trial" in report
    # ranked best-first
    metrics = [r["metric"] for r in records]
    assert metrics == sorted(metrics, reverse=True)
    # per-trial metric curves (reference W&B-report capability): every trial
    # streamed its JSONL tracker and the report renders the series
    assert "metrics/optimality over evaluations" in report
    curves = json.load(open(tmp_path / "sweep_out" / "curves.json"))
    assert set(curves) == {"0", "1"}
    assert all(len(v) >= 1 for v in curves.values())


def test_choice_is_u_driven():
    """choice maps the unit coordinate deterministically, so quasirandom and
    TPE cover discrete dims too (Ray's samplers do; rng-driven choice left
    them unadapted)."""
    p = ParamDef("k", "choice", [1, 5, 10])
    assert p.sample(0.0) == 1 and p.sample(0.5) == 5 and p.sample(0.99) == 10


def test_tpe_concentrates_on_optimum():
    """The in-repo bayesopt (TPE) must out-search random on a simple peaked
    objective: after warmup its proposals concentrate near the optimum."""
    from trlx_tpu.sweep import Searcher

    opt = np.array([0.7, 0.2])

    def objective(u):
        return -float(((u - opt) ** 2).sum())

    tpe = Searcher(2, "bayesopt", seed=3)
    history = []
    proposals = []
    for _ in range(40):
        u = tpe.propose(history)
        proposals.append(u)
        history.append(([float(x) for x in u], objective(u)))
    late = np.array(proposals[-10:])
    dist = np.abs(late - opt[None, :]).mean()
    assert dist < 0.15, f"late proposals not concentrated: mean|u-opt|={dist:.3f}\n{late}"
    # and adaptive algs refuse the non-feedback pregeneration path
    space = SweepSpace.from_config({"x": {"strategy": "uniform", "values": [0.0, 1.0]}})
    with pytest.raises(ValueError, match="adaptive"):
        list(space.trials(4, search_alg="bayesopt"))


def test_searcher_rejects_unknown_alg():
    from trlx_tpu.sweep import Searcher

    with pytest.raises(ValueError, match="not supported"):
        Searcher(2, "bohb9000")


def test_asha_successive_halving(tmp_path):
    """asha scheduler: rung populations shrink by reduction_factor while the
    budget dot-path grows by it, and the final rung runs at max_t."""
    script = tmp_path / "toy.py"
    script.write_text(textwrap.dedent("""
        import json, os, sys
        def main(hparams):
            x = hparams["method.init_kl_coef"]
            steps = hparams["train.total_steps"]
            # quality improves with budget; optimum at x=0.3
            score = -abs(x - 0.3) + 0.01 * steps
            out = os.environ.get("TRLX_TPU_SWEEP_RESULT")
            if out:
                with open(out, "w") as f:
                    json.dump({"stats": {"reward/mean": score}, "iter_count": steps}, f)
        if __name__ == "__main__":
            main(json.loads(sys.argv[1]) if len(sys.argv) > 1 else {})
    """))
    config = {
        "tune_config": {
            "mode": "max", "metric": "reward/mean", "search_alg": "random",
            "num_samples": 6, "scheduler": "asha",
            "grace_period": 2, "reduction_factor": 3, "max_t": 18,
        },
        "method.init_kl_coef": {"strategy": "uniform", "values": [0.0, 1.0]},
    }
    records = run_sweep(str(script), config, str(tmp_path / "out"), trial_timeout=60)
    by_rung = {}
    for r in records:
        by_rung.setdefault(r["rung"], []).append(r)
    assert sorted(by_rung) == [0, 1, 2]
    assert len(by_rung[0]) == 6 and len(by_rung[1]) == 2 and len(by_rung[2]) == 1
    assert all(r["hparams"]["train.total_steps"] == 2 for r in by_rung[0])
    assert all(r["hparams"]["train.total_steps"] == 6 for r in by_rung[1])
    assert by_rung[2][0]["hparams"]["train.total_steps"] == 18
    # the promoted survivor is the rung-1 winner's hparams
    rung1_best = max(by_rung[1], key=lambda r: r["metric"])
    assert by_rung[2][0]["hparams"]["method.init_kl_coef"] == rung1_best["hparams"]["method.init_kl_coef"]
    # ranked report exists
    assert (tmp_path / "out" / "report.md").exists()


def test_asha_promotions_resume_from_checkpoint(tmp_path):
    """Promoted trials continue from the previous rung's checkpoint instead
    of rerunning from scratch (VERDICT r2 #7): each config gets a private
    train.checkpoint_dir under the sweep dir and promotions set
    train.resume_from_checkpoint, so a promoted trial's iter_count continues
    where the rung left off."""
    script = tmp_path / "toy.py"
    script.write_text(textwrap.dedent("""
        import json, os, sys
        def main(hparams):
            steps = hparams["train.total_steps"]
            ckpt_dir = hparams.get("train.checkpoint_dir")
            start = 0
            if hparams.get("train.resume_from_checkpoint") and ckpt_dir:
                state = os.path.join(ckpt_dir, "state.json")
                assert os.path.exists(state), "promotion must find the rung ckpt"
                start = json.load(open(state))["iter_count"]
            # "train" from start to steps, checkpoint the final state
            assert ckpt_dir, "sweep must inject a per-config checkpoint dir"
            os.makedirs(ckpt_dir, exist_ok=True)
            with open(os.path.join(ckpt_dir, "state.json"), "w") as f:
                json.dump({"iter_count": steps}, f)
            out = os.environ.get("TRLX_TPU_SWEEP_RESULT")
            if out:
                with open(out, "w") as f:
                    json.dump({"stats": {"reward/mean": hparams["x"],
                                         "resumed_from": start},
                               "iter_count": steps}, f)
        if __name__ == "__main__":
            main(json.loads(sys.argv[1]) if len(sys.argv) > 1 else {})
    """))
    config = {
        "tune_config": {"mode": "max", "metric": "reward/mean", "num_samples": 4,
                        "scheduler": "asha", "grace_period": 2,
                        "reduction_factor": 2, "max_t": 8},
        "x": {"strategy": "uniform", "values": [0.0, 1.0]},
    }
    out_dir = tmp_path / "out"
    records = run_sweep(str(script), config, str(out_dir), trial_timeout=60)
    promoted = [r for r in records if r.get("rung", 0) >= 1]
    assert promoted, "expected at least one promotion"
    for r in promoted:
        # resumed exactly from the previous rung's final step, not 0
        prev_budget = r["hparams"]["train.total_steps"] // 2
        assert r["stats"]["resumed_from"] in (2, prev_budget)
        assert r["stats"]["resumed_from"] > 0
        assert r["hparams"]["train.resume_from_checkpoint"] is True
        assert r["hparams"]["train.checkpoint_dir"].startswith(str(out_dir))
        assert r["iter_count"] == r["hparams"]["train.total_steps"]
    # rung-0 trials each got a distinct private checkpoint dir
    rung0_dirs = {r["hparams"]["train.checkpoint_dir"] for r in records if r.get("rung") == 0}
    assert len(rung0_dirs) == 4


def test_asha_resume_optout(tmp_path):
    """asha_resume: false reruns promotions from scratch with no injected
    checkpoint keys (the round-2 behavior, kept as an explicit option)."""
    script = tmp_path / "toy.py"
    script.write_text(textwrap.dedent("""
        import json, os, sys
        def main(hparams):
            assert "train.checkpoint_dir" not in hparams
            assert "train.resume_from_checkpoint" not in hparams
            out = os.environ.get("TRLX_TPU_SWEEP_RESULT")
            if out:
                with open(out, "w") as f:
                    json.dump({"stats": {"reward/mean": hparams["x"]}}, f)
        if __name__ == "__main__":
            main(json.loads(sys.argv[1]) if len(sys.argv) > 1 else {})
    """))
    config = {
        "tune_config": {"mode": "max", "metric": "reward/mean", "num_samples": 2,
                        "scheduler": "asha", "grace_period": 2,
                        "reduction_factor": 2, "max_t": 4, "asha_resume": False},
        "x": {"strategy": "uniform", "values": [0.0, 1.0]},
    }
    records = run_sweep(str(script), config, str(tmp_path / "out"), trial_timeout=60)
    assert all(r["rc"] == 0 for r in records)


def test_parallel_trials_actually_overlap(tmp_path):
    """--max-concurrent N runs trials in a subprocess pool (VERDICT r2 #8):
    4 one-second trials at concurrency 4 finish in well under 4 seconds."""
    import time as _time

    script = tmp_path / "toy.py"
    script.write_text(textwrap.dedent("""
        import json, os, sys, time
        def main(hparams):
            time.sleep(1.0)
            out = os.environ.get("TRLX_TPU_SWEEP_RESULT")
            if out:
                with open(out, "w") as f:
                    json.dump({"stats": {"reward/mean": hparams["x"]}}, f)
        if __name__ == "__main__":
            main(json.loads(sys.argv[1]) if len(sys.argv) > 1 else {})
    """))
    config = {
        "tune_config": {"mode": "max", "metric": "reward/mean",
                        "num_samples": 4, "search_alg": "random"},
        "x": {"strategy": "uniform", "values": [0.0, 1.0]},
    }
    t0 = _time.time()
    records = run_sweep(
        str(script), config, str(tmp_path / "out"), trial_timeout=60,
        extra_env={"JAX_PLATFORMS": "cpu"}, max_concurrent=4,
    )
    elapsed = _time.time() - t0
    assert len(records) == 4 and all(r["rc"] == 0 for r in records)
    # wall clock must be well under the sum of per-trial runtimes (startup
    # cost per trial is environment-dependent, so the bound is relative)
    total_runtime = sum(r["runtime_s"] for r in records)
    assert elapsed < 0.55 * total_runtime, (
        f"trials did not overlap: wall={elapsed:.1f}s vs sum={total_runtime:.1f}s"
    )
    # trial indices and result files all distinct
    assert sorted(r["trial"] for r in records) == [0, 1, 2, 3]
    assert all(r["metric"] is not None for r in records)


def test_parallel_trials_serialize_on_accelerator(tmp_path, caplog):
    """Concurrency without CPU-mesh trials would contend for the single
    accelerator — the sweep must serialize automatically."""
    script = tmp_path / "toy.py"
    script.write_text(textwrap.dedent("""
        import json, os, sys
        def main(hparams):
            out = os.environ.get("TRLX_TPU_SWEEP_RESULT")
            if out:
                with open(out, "w") as f:
                    json.dump({"stats": {"reward/mean": 1.0}}, f)
        if __name__ == "__main__":
            main(json.loads(sys.argv[1]) if len(sys.argv) > 1 else {})
    """))
    config = {
        "tune_config": {"mode": "max", "metric": "reward/mean", "num_samples": 2},
        "x": {"strategy": "uniform", "values": [0.0, 1.0]},
    }
    import os as _os
    env_backup = _os.environ.pop("JAX_PLATFORMS", None)
    try:
        records = run_sweep(
            str(script), config, str(tmp_path / "out"), trial_timeout=60,
            max_concurrent=4,
        )
    finally:
        if env_backup is not None:
            _os.environ["JAX_PLATFORMS"] = env_backup
    assert len(records) == 2 and all(r["rc"] == 0 for r in records)


def test_asha_requires_max_t(tmp_path):
    config = {
        "tune_config": {"scheduler": "hyperband", "num_samples": 2},
        "x": {"strategy": "uniform", "values": [0.0, 1.0]},
    }
    with pytest.raises(ValueError, match="max_t"):
        run_sweep("does_not_matter.py", config, str(tmp_path / "out"))


def test_asha_lone_survivor_runs_at_max_t(tmp_path):
    """When the population collapses to one survivor early, it jumps straight
    to the full max_t budget (review regression: the winner must always get
    its final-budget run)."""
    script = tmp_path / "toy.py"
    script.write_text(textwrap.dedent("""
        import json, os, sys
        def main(hparams):
            out = os.environ.get("TRLX_TPU_SWEEP_RESULT")
            if out:
                with open(out, "w") as f:
                    json.dump({"stats": {"reward/mean": hparams["x"]}}, f)
        if __name__ == "__main__":
            main(json.loads(sys.argv[1]) if len(sys.argv) > 1 else {})
    """))
    config = {
        "tune_config": {"mode": "max", "metric": "reward/mean", "num_samples": 3,
                        "scheduler": "asha", "grace_period": 2,
                        "reduction_factor": 3, "max_t": 18,
                        "budget_key": "train.total_steps"},
        "x": {"strategy": "uniform", "values": [0.0, 1.0]},
    }
    records = run_sweep(str(script), config, str(tmp_path / "out"), trial_timeout=60)
    final = [r for r in records if r["rung"] == 1]
    assert len(final) == 1
    assert final[0]["hparams"]["train.total_steps"] == 18


@pytest.mark.slow
def test_two_process_trials_dispatch(tmp_path):
    """Cluster-dispatch leg (round-3 verdict next#7, reference
    ``trlx/sweep.py:267-348`` Ray placement): each trial runs as its OWN
    2-process ``jax.distributed`` cluster over the
    TRLX_TPU_COORDINATOR/NUM_PROCESSES/PROCESS_ID contract, placed through a
    command-template launcher (env(1) carries the per-process contract the
    way a remote shell would), rank 0 the only result writer."""
    import textwrap

    script = tmp_path / "trial_script.py"
    script.write_text(
        textwrap.dedent(
            """
            import json, os, sys

            def main(hparams):
                import trlx_tpu.trlx as trlx
                trlx.initialize_runtime()
                import jax
                import jax.numpy as jnp
                from jax.experimental import multihost_utils

                assert jax.process_count() == 2, jax.process_count()
                total = multihost_utils.process_allgather(
                    jnp.asarray(1.0 + jax.process_index())
                )
                # metric depends on the swept hparam AND the collective
                metric = float(total.sum()) * float(hparams["optimizer.kwargs.lr"])
                if jax.process_index() == 0:
                    with open(os.environ["TRLX_TPU_SWEEP_RESULT"], "w") as f:
                        json.dump(
                            {"stats": {"reward/mean": metric}, "iter_count": 1}, f
                        )

            if __name__ == "__main__":
                main(json.loads(sys.argv[1]))
            """
        )
    )
    config = {
        "tune_config": {
            "mode": "max",
            "metric": "reward/mean",
            "search_alg": "quasirandom",
            "num_samples": 2,
            "procs_per_trial": 2,
            "launcher": "env {env} {python} {script} {hparams}",
        },
        "optimizer.kwargs.lr": {"strategy": "loguniform", "values": [1e-4, 1e-3]},
    }
    records = run_sweep(
        str(script),
        config,
        str(tmp_path / "out"),
        trial_timeout=600,
        extra_env={"TRLX_TPU_PLATFORM": "cpu", "TRLX_TPU_NO_TQDM": "1"},
    )
    assert len(records) == 2
    for r in records:
        log = open(str(tmp_path / "out" / f"trial_{r['trial']:03d}.log")).read()
        assert r["rc"] == 0, log[-2000:]
        # allgather total = 1 + 2 = 3; metric = 3 * lr from the result file
        lr = r["hparams"]["optimizer.kwargs.lr"]
        assert abs(r["metric"] - 3.0 * lr) < 1e-9, (r["metric"], lr)
    assert [r["metric"] for r in records] == sorted(
        (r["metric"] for r in records), reverse=True
    )


def test_accelerator_trial_processes_need_a_host_each(tmp_path, monkeypatch):
    """A chip belongs to one process: two accelerator processes of one trial
    on one host are refused before anything is launched (CPU trials, as in
    the multi-process test above, may share a host)."""
    from trlx_tpu.sweep import run_trial

    monkeypatch.delenv("TRLX_TPU_PLATFORM", raising=False)
    with pytest.raises(ValueError, match="need a host each"):
        run_trial(
            "unused.py", {}, str(tmp_path / "r.json"), str(tmp_path / "t.log"),
            extra_env={"JAX_PLATFORMS": "tpu"}, procs_per_trial=2,
        )
    assert not (tmp_path / "t.log").exists()


def test_hosts_require_launcher(tmp_path):
    with pytest.raises(ValueError, match="launcher"):
        run_sweep(
            __file__,
            {
                "tune_config": {"hosts": ["a", "b"]},
                "x": {"strategy": "uniform", "values": [0.0, 1.0]},
            },
            str(tmp_path / "out2"),
        )


def test_sparkline_and_wandb_fallback(tmp_path, monkeypatch):
    from trlx_tpu.sweep import _sparkline, publish_wandb_report

    assert _sparkline([0.0, 0.5, 1.0]) == "▁▄█"
    assert _sparkline([]) == ""
    assert _sparkline([2.0, 2.0]) == "▁▁"
    assert " " in _sparkline([0.0, float("nan"), 1.0])
    # wandb absent or disabled -> clean no-op, never an exception
    monkeypatch.setenv("WANDB_MODE", "disabled")
    assert publish_wandb_report([], {}, "m", str(tmp_path)) is False


def test_trial_command_launcher_template_robustness():
    """Launcher templates substitute ONLY the known {tokens}; every other
    brace construct — ${HOME}, ${arr[0]}, ${VAR:-default}, awk {print},
    lone braces — passes through verbatim, and extra_env keys ride {env}
    (advisor round-4 findings)."""
    from trlx_tpu.sweep import _trial_command

    env = {
        "TRLX_TPU_SWEEP_RESULT": "/tmp/r.json",
        "WANDB_API_KEY": "secret",
        "XLA_FLAGS": "--foo",
        "UNRELATED": "no",
    }
    cmd = _trial_command(
        'ssh {host} \'echo ${HOME} ${arr[0]} ${VAR:-/tmp} { | awk {print}\' '
        "env {env} {python} {script} {hparams}",
        __file__, {"a": 1}, "h1", env, extra_keys=("WANDB_API_KEY", "XLA_FLAGS"),
    )
    for construct in ("${HOME}", "${arr[0]}", "${VAR:-/tmp}", "{ |", "{print}"):
        assert construct in cmd, (construct, cmd)
    assert "ssh h1" in cmd
    assert "WANDB_API_KEY=secret" in cmd and "XLA_FLAGS=--foo" in cmd
    assert "UNRELATED" not in cmd  # non-contract env never leaks
    assert "TRLX_TPU_SWEEP_RESULT=/tmp/r.json" in cmd


def test_trial_command_warns_on_placeholder_near_miss(trlx_log_records):
    """A typo'd placeholder ({pyhton}, {hparam}, {HOST}) survives
    substitution silently into the shell line — the builder now flags it;
    genuine shell/awk braces stay silent (advisor r5)."""
    from trlx_tpu.sweep import _trial_command

    def warnings_for(launcher):
        trlx_log_records.clear()
        _trial_command(launcher, __file__, {"a": 1}, "h1", {})
        return [
            r.getMessage() for r in trlx_log_records if r.levelname == "WARNING"
        ]

    # exact tokens substitute: nothing survives, nothing warns
    assert warnings_for("{python} {script} {hparams}") == []
    # near misses: typo, missing plural, wrong case
    for bad, hint in (("{pyhton}", "python"), ("{hparam}", "hparams"), ("{HOST}", "host")):
        msgs = warnings_for(f"{bad} {{script}} {{hparams}}")
        assert len(msgs) == 1 and bad.strip("{}") in msgs[0] and hint in msgs[0], (
            bad, msgs
        )
    # warn-once per template: a 200-trial sweep diagnoses its typo once
    assert warnings_for("{pyhton} {script} {hparams}") == []
    # shell/awk constructs that merely *look* braced stay silent
    assert warnings_for(
        "ssh {host} 'echo ${HOME} ${arr[0]} ${VAR:-/tmp} | awk {print}' "
        "{python} {script} {hparams}"
    ) == []
    # brace text inside substituted VALUES is the user's business: only the
    # template is scanned
    trlx_log_records.clear()
    from trlx_tpu.sweep import _trial_command as tc

    tc("{python} {script} {hparams}", __file__, {"fmt": "{host} {pyhton}"}, "h1", {})
    assert [r for r in trlx_log_records if r.levelname == "WARNING"] == []
