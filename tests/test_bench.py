"""bench.py glue that must hold before the first real run: the gpt2-xl
stage's gates and the program-FLOPs accounting. That bench.py refuses a
platform other than the TPU is pinned in tests/test_chip_smoke.py."""

import importlib.util
import os

import pytest


@pytest.fixture()
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(__file__), "..", "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_xl_stage_skips_on_cpu(bench, capsys):
    bench._maybe_xl_stage(True, float("nan"), None)
    assert "xl_stage" not in capsys.readouterr().err


def test_xl_stage_respects_deadline(bench, monkeypatch, capsys):
    monkeypatch.setenv("BENCH_XL_DEADLINE_S", "1")
    monkeypatch.setattr(bench, "_T0", bench.time.time() - 100)  # budget gone
    bench._maybe_xl_stage(False, 275e12, None)
    err = capsys.readouterr().err
    assert "skipping gpt2-xl stage" in err and "xl_stage" not in err


def test_xl_stage_env_kill_switch(bench, monkeypatch, capsys):
    monkeypatch.setenv("BENCH_XL", "0")
    monkeypatch.setattr(bench, "_T0", bench.time.time())
    bench._maybe_xl_stage(False, 275e12, None)
    assert capsys.readouterr().err == ""


@pytest.mark.slow
def test_program_cycle_flops_glue(bench):
    """The on-chip MFU accounting path (hot_program_costs over the live
    trainer) must produce a positive FLOPs total — exercised here on CPU so
    the first run on the chip is not the first time this code runs."""
    from trlx_tpu.trainer import get_trainer
    import trlx_tpu.trainer.ppo  # noqa: F401

    chunk = 8  # must shard over the conftest mesh's data axes (8)
    config = bench._bench_ppo_config(
        "builtin:gpt2-test", chunk, "/tmp/bench_glue_ckpt"
    )
    trainer = get_trainer(config.train.trainer)(
        config=config,
        reward_fn=lambda **kw: [0.0] * chunk,
        metric_fn=None,
        stop_sequences=[],
        abstract_init=True,
    )
    flops = bench._program_cycle_flops(config, trainer, chunk)
    assert flops is not None and flops > 0, flops
    # a non-sharding chunk must REFUSE (per-device accounting would
    # overcount by up to n_dev x), not emit an inflated number
    assert bench._program_cycle_flops(config, trainer, chunk - 1) is None
