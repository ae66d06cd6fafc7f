"""Config-system tests (shape of the reference's ``tests/test_configs.py``)."""

import glob
import os

import pytest
import yaml

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.default_configs import (
    default_grpo_config,
    default_ilql_config,
    default_ppo_config,
    default_sft_config,
)

DEFAULTS = [default_ppo_config, default_ilql_config, default_sft_config]


@pytest.mark.parametrize("make", DEFAULTS)
def test_default_config_roundtrip(make):
    config = make()
    restored = TRLConfig.from_dict(config.to_dict())
    assert restored.to_dict() == config.to_dict()


@pytest.mark.parametrize("make", DEFAULTS)
def test_yaml_roundtrip(tmp_path, make):
    config = make()
    path = os.path.join(tmp_path, "config.yml")
    with open(path, "w") as f:
        yaml.dump(config.to_dict(), f)
    assert TRLConfig.load_yaml(path).to_dict() == config.to_dict()


def test_repo_configs_load():
    """Every YAML under configs/ and examples/**/configs must load."""
    root = os.path.join(os.path.dirname(__file__), "..")
    paths = glob.glob(os.path.join(root, "configs", "*.yml"))
    paths += glob.glob(os.path.join(root, "examples", "**", "configs", "*.yml"), recursive=True)
    for path in paths:
        config = TRLConfig.load_yaml(path)
        assert config.train.entity_name is None, f"entity leaked in {path}"


def test_dot_path_update():
    config = default_ppo_config()
    updated = TRLConfig.update(config, {"train.seed": 42, "method.gamma": 0.5})
    assert updated.train.seed == 42
    assert updated.method.gamma == 0.5


def test_dot_path_update_unknown_key_raises():
    config = default_ppo_config()
    with pytest.raises(ValueError):
        TRLConfig.update(config, {"train.nonexistent_field_xyz": 1})


def test_evolve_nested():
    config = default_ilql_config()
    evolved = config.evolve(method=dict(gamma=0.98, gen_kwargs=dict(max_new_tokens=100)))
    assert evolved.method.gamma == 0.98
    assert evolved.method.gen_kwargs["max_new_tokens"] == 100
    # untouched leaves preserved
    assert evolved.method.gen_kwargs["top_k"] == config.method.gen_kwargs["top_k"]
    assert config.method.gamma == 0.99  # original unchanged


def test_strict_from_dict_rejects_unknown():
    config = default_ppo_config().to_dict()
    config["model"]["bogus_key"] = 1
    with pytest.raises(ValueError):
        TRLConfig.from_dict(config)


@pytest.mark.parametrize("make", [default_ppo_config, default_grpo_config])
def test_method_loss_kernel_is_an_unknown_key(make):
    """The option went with the fused learner kernel (PR 45): a config that
    still sets it fails as any unknown key does, and no default carries it."""
    assert not hasattr(make().method, "loss_kernel")
    config = make().to_dict()
    config["method"]["loss_kernel"] = "xla"
    with pytest.raises(ValueError, match=r"Unknown keys \['loss_kernel'\]"):
        TRLConfig.from_dict(config)


def test_parallel_config_defaults():
    config = default_ppo_config()
    assert config.parallel.data == -1
    assert config.parallel.compute_dtype == "bfloat16"


def test_update_top_level_scalar_key_raises():
    """Non-dotted unknown keys must error, not be silently dropped."""
    config = default_ppo_config()
    with pytest.raises(ValueError):
        TRLConfig.update(config, {"seed": 0})


def test_scheduler_warmup_cosine_peak_not_conflated():
    from trlx_tpu.utils import get_scheduler

    sched = get_scheduler(
        "warmup_cosine",
        {"init_value": 0.0, "peak_value": 1e-4, "warmup_steps": 10, "decay_steps": 100},
    )
    assert float(sched(10)) == pytest.approx(1e-4)
    assert float(sched(0)) == pytest.approx(0.0)
