"""The nemotron_h family's own counts, checked without a chip.

    python3 -m pytest chipbench/tests -q        # from the root of the repository, JAX_PLATFORMS=cpu

``chipbench/costs/nemotron_h.py``: a layer of each kind by hand at the
published widths (nothing for a sublayer a layer does not have); a cycle of
the cell's rows through ``mamba_step``, ``mamba_scan`` and the flash phases of
the one attention layer; and the walk against the plain reference's own
products at the toy widths (``test_flops.py`` charges EVERY layer a score
square, which eight of this family's nine layers do not have: its case for this
configuration is an expected failure in tier 1, and this file holds the count).
"""

import os
import types

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import flops, ssm_costs  # noqa: E402
from chipbench.costs import nemotron_h as costs  # noqa: E402

PATTERN = "MEMEM*EME"
LAYOUTS = {"M": ("mamba2", "none"), "*": ("attention", "none"), "E": ("none", "moe")}
PUBLISHED = types.SimpleNamespace(
    num_heads=32, kv_heads=2, dims_per_head=128, v_head_dim=None, num_experts_per_tok=6, mixer="none",
    mamba_heads=64, mamba_head_dim=64, mamba_state=128, mamba_groups=8, mamba_chunk=128, mamba_conv=4, mamba_conv_channels=6144,
    layer_layout=lambda i: types.SimpleNamespace(mixer=LAYOUTS[PATTERN[i]][0], ffn=LAYOUTS[PATTERN[i]][1], window=None))


def sds(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


TREES = {
    "M": {"ln_attn": {"scale": sds(2688)}, "mixer": {
        "in_proj": {"kernel": sds(2688, 10304)}, "out_proj": {"kernel": sds(4096, 2688)}, "conv_weight": sds(4, 6144),
        "conv_bias": sds(6144), "A_log": sds(64), "D": sds(64), "dt_bias": sds(64), "norm_scale": sds(4096)}},
    "*": {"ln_attn": {"scale": sds(2688)}, "attn": {
        "q_proj": {"kernel": sds(2688, 4096)}, "k_proj": {"kernel": sds(2688, 256)}, "v_proj": {"kernel": sds(2688, 256)},
        "o_proj": {"kernel": sds(4096, 2688)}}},
    "E": {"ln_mlp": {"scale": sds(2688)}, "mlp": {
        "router": {"kernel": sds(2688, 128)}, "router_bias": sds(128), "w_up": sds(8, 2688, 1856), "w_down": sds(8, 1856, 2688),
        "shared_expert": {"up_proj": {"kernel": sds(2688, 3712)}, "down_proj": {"kernel": sds(3712, 2688)}}}},
}


def model_of(tcfg=PUBLISHED, layers=9, ref_layers=(7, 8)):
    return types.SimpleNamespace(tcfg=tcfg, n_layers=layers, lowest_trained=-1, ref_layers=list(ref_layers), epochs=1, act_bytes=2)


def test_a_layer_is_charged_its_one_sublayer_and_nothing_else():
    t, f = 1152, 0.0625
    m = costs.layer_forward(PUBLISHED, 0, TREES["M"], t, {})
    assert set(m["matmuls"]) == {("mixer", "in_proj", "kernel"), ("mixer", "out_proj", "kernel"), ("mixer", "conv_weight")}
    assert m["matmuls"]["mixer", "in_proj", "kernel"] == 2 * 2688 * 10304 * t and m["matmuls"]["mixer", "conv_weight"] == 2 * 4 * 6144 * t
    chunk = 8 * 2 * 128 * 128 * 128 + 64 * 2 * 128 * 128 * 64 + 64 * 4 * 128 * 64 * 128  # scores a group, their product a head, the two state products
    assert m["mix"] == 9 * chunk == costs.scan_flops(PUBLISHED, t)
    a = costs.layer_forward(PUBLISHED, 5, TREES["*"], t, {})
    assert a["mix"] == 2 * 32 * (128 + 128) * t * (t + 1) / 2 and sum(a["matmuls"].values()) == 2 * (2 * 2688 * 4096 + 2 * 2688 * 256) * t
    e = costs.layer_forward(PUBLISHED, 1, TREES["E"], t, {"moe/held_frac": f})
    assert e["mix"] == 0.0
    assert e["matmuls"]["mlp", "w_up"] == e["matmuls"]["mlp", "w_down"] == 2 * 2688 * 1856 * 6 * f * t  # two 3-D leaves, 6 x held_frac of them a token
    assert e["matmuls"]["mlp", "shared_expert", "up_proj", "kernel"] == 2 * 2688 * 3712 * t and e["matmuls"]["mlp", "router", "kernel"] == 2 * 2688 * 128 * t
    assert [costs.kind(PUBLISHED, i) for i in range(9)] == list(PATTERN)


def test_the_cycles_decode_floor_is_the_four_states_read_and_written_every_required_step():
    cycle = {"row_lengths": [(128, 1024)] * 128, "steps": [{}] * 16}
    (phase,) = costs.mamba_step(model_of(), cycle)
    step = ssm_costs.step_costs(1, 64, 64, 128, 8)
    assert step["state_bytes"] == 4 * 64 * 64 * 128 == 2**21 and step["bytes"] == 2 * 2**21 + 2 * (2 * 4096 + 2 * 1024 + 64)
    assert phase == {"phase": "decode", "flops": 4 * 128 * 1023 * 6.0 * 64 * 64 * 128, "bytes": 4 * 128 * 1023 * step["bytes"]}
    floor = flops.floor_seconds([phase], {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, 1)
    assert 2.6 < floor < 2.8  # 2.69 s a cycle on one v5e, 2.63 ms a step: bound by the bytes
    assert costs.mamba_step(model_of(), {"row_lengths": [(128, 1)] * 4}) == []


def test_the_scan_and_the_flash_phases_run_on_their_own_layers_alone():
    cycle = {"row_lengths": [(128, 1024)] * 128, "steps": [{}] * 16}
    scan = {p["phase"]: p for p in costs.mamba_scan(model_of(), cycle)}
    assert list(scan) == ["prefill", "score", "score_reference", "train_forward", "train_backward"]
    row = ssm_costs.scan_costs(1, 1152, 64, 64, 128, 8, chunk=128)
    assert scan["score"]["flops"] == 4 * 128 * row["flops"] and scan["score_reference"]["flops"] == 1 * 128 * row["flops"]  # layer 7 alone of the branch's two
    assert scan["train_backward"]["flops"] == 2 * scan["train_forward"]["flops"] and scan["prefill"]["flops"] == 4 * 128 * ssm_costs.scan_costs(1, 128, 64, 64, 128, 8)["flops"]
    fwd = {p["phase"]: p for p in costs.flash_fwd(model_of(), cycle)}
    assert list(fwd) == ["prefill", "score", "train_forward"]  # the reference branch (layers 7, 8) holds no attention layer
    assert fwd["score"]["flops"] == 128 * 2 * 32 * 256 * flops.pairs(1152, None)
    (bwd,) = costs.flash_bwd(model_of(), cycle)
    assert bwd["flops"] == 2 * fwd["train_forward"]["flops"]
    assert costs.flash_bwd(model_of(layers=5), cycle) == []  # MEMEM: no attention layer, no phase


def test_forward_count_is_the_references_matmuls_in_all_three_kinds_of_layer():
    """``test_flops.py``'s check of the forward count for a stack of
    one-sublayer layers: the family's ``layer_forward`` against the products
    in the reference's own jaxpr at the toy widths. The reference multiplies
    every projection, every HELD expert for every token, the attention
    layer's full score square; the conv is element-wise there and the
    recurrence runs token by token (a ``scan`` of the row's length, left out
    of both sides)."""
    from chipbench.checks import backbone_of
    from chipbench.tests.test_flops import HELD_FRAC, Q, R, _toy_trainer, dot_flops

    trainer, config_file = _toy_trainer("nemotron3-nano-30b-a3b-l9e8")
    model, tcfg, t = flops.Model(trainer, "nemotron_h"), trainer.tcfg, Q + R
    assert model.layer_forward is costs.layer_forward and list(model.head) == [("lm_head", "kernel")]
    stats, expected = {"moe/held_frac": HELD_FRAC}, 0.0
    for i in range(model.n_layers):
        cost = model.layer(i, t, stats)
        shapes = dict(flops._leaves(model.layers[i]))
        for path, value in cost["matmuls"].items():
            if path == costs.CONV_UNDER:
                continue
            if len(shapes[path]) == 3:  # every held expert on every token, not k x held_frac of them
                value *= shapes[path][0] / (tcfg.num_experts_per_tok * HELD_FRAC)
            expected += value
        if costs.kind(tcfg, i) == "*":
            expected += cost["mix"] * (t * t) / flops.pairs(t, None)
        else:
            assert (cost["mix"] > 0) == (costs.kind(tcfg, i) == "M")
    expected += sum(2.0 * a * b * R for a, b in model.head.values())
    params = backbone_of(trainer.state.params)
    ids, mask = jnp.zeros((1, t), jnp.int32), jnp.ones((1, t), jnp.int32)
    ref = __import__("chipbench.reference.nemotron_h", fromlist=["logits"])
    jaxpr = jax.make_jaxpr(lambda p, i, m: ref.logits(p, config_file["published"], i, m, (Q, t)))(params, ids, mask)
    assert dot_flops(jaxpr.jaxpr, skip_scans_of=t) == pytest.approx(expected, rel=0.02)
