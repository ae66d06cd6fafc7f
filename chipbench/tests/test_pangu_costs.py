"""The pangu_ultra_moe family's own kernel cost, checked without a chip.

    python3 -m pytest chipbench/tests -q        # from the root of the repository, JAX_PLATFORMS=cpu

``chipbench/costs/pangu_ultra_moe.py::latent_decode`` by hand at the published
widths, where it sits against the chip's ridge, and that a model without a
latent cache gives it nothing to count.
"""

import os
import types

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from chipbench import flops  # noqa: E402
from chipbench.costs import pangu_ultra_moe as costs  # noqa: E402

PUBLISHED = types.SimpleNamespace(num_heads=128, kv_lora_rank=512, qk_rope_head_dim=64,
                                  qk_nope_head_dim=128, v_head_dim=128, dims_per_head=192)


def model_of(tcfg, layers):
    return types.SimpleNamespace(tcfg=tcfg, n_layers=layers, act_bytes=2)


@pytest.mark.parametrize("s", [129, 384, 640])
def test_a_row_a_layer_a_step_by_hand(s):
    got = costs.latent_decode_row_step(PUBLISHED, s, 2)
    assert got["flops"] == 2 * 128 * (576 + 512) * s + 4 * 128 * 128 * 512
    assert got["bytes"] == 1152 * (s + 1)
    # 128 heads on one latent: a long row's step comes down to a v5e's ridge
    # (2 x 128 x 1088 / 1152 = 242 operations a byte; the chip's 197e12 / 819e9 = 240)
    assert 241.7 < got["flops"] / got["bytes"] < {129: 464, 384: 317, 640: 287}[s]


def test_the_cycle_is_every_required_step_of_every_row_in_every_layer():
    cycle = {"row_lengths": [(128, 512)] * 64}
    (phase,) = costs.latent_decode(model_of(PUBLISHED, 5), cycle)
    steps = range(511)  # the prefill gives the first token
    flops_row = sum(2 * 128 * 1088 * (128 + i + 1) + 4 * 128 * 128 * 512 for i in steps)
    bytes_row = sum(1152 * (128 + i + 2) for i in steps)
    assert phase == {"phase": "decode", "flops": 5 * 64 * flops_row, "bytes": 5 * 64 * bytes_row}
    floor = flops.floor_seconds([phase], {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, 1)
    assert 0.10 < floor < 0.13  # 117 ms a cycle on one v5e


def test_rows_of_unlike_lengths_and_rows_with_nothing_to_decode():
    cycle = {"row_lengths": [(4, 3), (6, 1), (2, 0)]}
    (phase,) = costs.latent_decode(model_of(PUBLISHED, 1), cycle)
    assert phase["bytes"] == 1152 * ((4 + 2) + (4 + 3))  # the first row's two steps; the others take none
    assert costs.latent_decode(model_of(PUBLISHED, 1), {"row_lengths": [(6, 1)]}) == []


def test_a_model_without_a_latent_cache_has_nothing_to_count():
    dense = types.SimpleNamespace(num_heads=32, kv_lora_rank=0)
    assert costs.latent_decode(model_of(dense, 4), {"row_lengths": [(128, 512)]}) == []
    assert costs.latent_decode(model_of(types.SimpleNamespace(num_heads=32), 4), {"row_lengths": [(128, 512)]}) == []


def test_the_metric_file_names_this_function_and_the_harness_finds_it():
    import json

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "layer_metrics", "latent_decode_roofline.json")) as f:
        spec = json.load(f)
    assert spec["reducer"] == "trace_op_roofline" and spec["costs"] == "latent_decode"
    model = types.SimpleNamespace(family=flops.family_module("pangu_ultra_moe"))
    assert flops.kernel_costs("latent_decode", model) is costs.latent_decode
    assert not hasattr(costs, "layer_forward")  # the generic walk reads this family's tree
