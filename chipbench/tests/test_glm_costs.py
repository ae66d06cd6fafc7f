"""The glm_moe_dsa family's own counts, checked without a chip.

    python3 -m pytest chipbench/tests -q        # from the root of the repository, JAX_PLATFORMS=cpu

``chipbench/costs/glm_moe_dsa.py``: a row, a layer, a decode step by hand at
the published widths; a cycle of the cell's rows; the long passes' count (the
chosen pairs, the index scores of every causal pair on a ``full`` layer, no
backward pass for the indexer) against the generic walk's and against the
plain reference's own products (``test_flops.py`` holds the forward count of
the toy to the reference's ``dot_general``s for every configuration of
``BENCHMARK.json``, this one among them).
"""

import json
import os
import types

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from chipbench import flops  # noqa: E402
from chipbench.costs import glm_moe_dsa as costs  # noqa: E402

TYPES = ("full", "shared", "shared", "shared", "full")
PUBLISHED = types.SimpleNamespace(
    num_heads=64, kv_heads=64, kv_lora_rank=512, qk_rope_head_dim=64, qk_nope_head_dim=192, v_head_dim=256,
    dims_per_head=256, index_topk=2048, index_heads=32, index_head_dim=128, num_experts_per_tok=8, mixer="none",
    layer_layout=lambda i: types.SimpleNamespace(indexer=TYPES[i], window=None))


def model_of(tcfg, layers):
    return types.SimpleNamespace(tcfg=tcfg, n_layers=layers, act_bytes=2)


@pytest.mark.parametrize("s", [1000, 7169, 8192])
@pytest.mark.parametrize("full", [True, False], ids=["full", "shared"])
def test_a_row_a_layer_a_step_by_hand(s, full):
    got = costs.sparse_decode_row_step(PUBLISHED, s, full, 2)
    kept = min(s, 2048)
    attention = 2 * 64 * (576 + 512) * kept + 2 * 64 * 512 * (192 + 256)
    assert got["flops"] == attention + (2 * 32 * 128 * s if full else 0)
    assert got["bytes"] == 1152 * kept + (256 * s if full else 0)
    if s > 2048:  # past index_topk the attention no longer grows with the row; the index pass does
        longer = costs.sparse_decode_row_step(PUBLISHED, s + 100, full, 2)
        assert longer["flops"] - got["flops"] == (2 * 32 * 128 * 100 if full else 0)


def test_the_cycle_is_every_required_step_of_every_row_in_every_layer_by_its_kind():
    cycle = {"row_lengths": [(7168, 1024)] * 8}
    (phase,) = costs.sparse_decode(model_of(PUBLISHED, 5), cycle)
    steps = range(1023)  # the prefill gives the first token
    attention = 2 * 64 * 1088 * 2048 + 2 * 64 * 512 * 448
    flops_row = sum(5 * attention + 2 * 2 * 32 * 128 * (7168 + i + 1) for i in steps)
    bytes_row = sum(5 * 1152 * 2048 + 2 * 256 * (7168 + i + 1) for i in steps)
    assert phase == {"phase": "decode", "flops": 8 * flops_row, "bytes": 8 * bytes_row}
    floor = flops.floor_seconds([phase], {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, 1)
    assert 0.15 < floor < 0.17  # 0.157 s a cycle on one v5e: 0.154 ms a step, bound by the bytes


def test_rows_with_nothing_to_decode_and_a_model_without_a_selection():
    assert costs.sparse_decode(model_of(PUBLISHED, 5), {"row_lengths": [(6, 1), (2, 0)]}) == []
    (phase,) = costs.sparse_decode(model_of(PUBLISHED, 1), {"row_lengths": [(4, 3), (6, 1)]})
    assert phase["bytes"] == (1152 + 256) * (5 + 6)  # the first row's two steps on one full layer
    dense = types.SimpleNamespace(num_heads=32, index_topk=0)
    assert costs.sparse_decode(model_of(dense, 4), {"row_lengths": [(128, 512)]}) == []
    assert costs.sparse_decode(model_of(types.SimpleNamespace(num_heads=32), 4), {"row_lengths": [(128, 512)]}) == []


def layer_tree(full):
    import numpy as np

    leaf = lambda *shape: {"kernel": np.zeros(shape, np.float32)}
    attn = {"q_a_proj": leaf(6144, 2048), "q_b_proj": leaf(2048, 16384), "kv_a_proj": leaf(6144, 576),
            "kv_b_proj": leaf(512, 28672), "o_proj": leaf(16384, 6144)}
    if full:
        attn["indexer"] = {"wq_b": leaf(2048, 4096), "wk": leaf(6144, 128), "weights_proj": leaf(6144, 32),
                           "k_norm": {"scale": np.zeros(128), "bias": np.zeros(128)}}
    mlp = {"router": leaf(6144, 256), "router_bias": np.zeros(256),
           "shared_expert": {n: leaf(*s) for n, s in (("gate_proj", (6144, 2048)), ("up_proj", (6144, 2048)), ("down_proj", (2048, 6144)))},
           "w_gate": np.zeros((8, 6144, 2048)), "w_up": np.zeros((8, 6144, 2048)), "w_down": np.zeros((8, 2048, 6144))}
    return {"attn": attn, "mlp": mlp}


@pytest.mark.parametrize("t", [1500, 8192])
@pytest.mark.parametrize("i", [1, 4], ids=["shared", "full"])
def test_a_long_pass_counts_the_chosen_pairs_and_the_index_scores_by_hand(i, t):
    """ISSUE 42's arithmetic at 8192: 428 MFLOP a token of matmuls in a
    sparse layer with a thirty-second of the routing held, 2.2 TFLOP of causal
    pairs a row a layer, 0.96 of chosen ones, 0.27 of index scores."""
    stats = {"moe/held_frac": 8 / 256}
    tree = layer_tree(full=i == 4)
    generic = flops.generic_layer_forward(PUBLISHED, i, tree, t, stats)
    cost = costs.layer_forward(PUBLISHED, i, tree, t, stats)
    causal = t * (t + 1) / 2
    chosen = causal if t <= 2048 else 2048 * 2049 / 2 + (t - 2048) * 2048
    attention = 2 * 64 * 512 * chosen
    projections = scores = 0.0
    if i == 4:
        projections = 2 * (2048 * 4096 + 6144 * 128 + 6144 * 32) * t
        scores = 2 * 32 * 128 * causal
    walked = sum(generic["matmuls"].values())  # every 2-D kernel, the indexer's three among them, and the experts
    assert sum(cost["matmuls"].values()) + cost["mix"] == pytest.approx(walked + attention + scores, rel=1e-12)
    # what a layer above the lowest trained leaf is charged for its backward pass (its matmuls
    # once more, its mix twice): the projections and the chosen pairs, and nothing for the indexer
    charged = sum(cost["matmuls"].values()) + flops.MIX_BACKWARD * cost["mix"]
    assert charged == pytest.approx(walked - projections + 2 * attention, rel=1e-12)
    assert set(cost["matmuls"]) == set(generic["matmuls"])  # every key a path of the tree
    if t == 8192:
        per_token = sum(v for p, v in generic["matmuls"].items() if "indexer" not in p) / t
        assert per_token == pytest.approx(428e6, rel=0.005)
        assert generic["mix"] == pytest.approx(2.2e12, rel=0.01) and attention == pytest.approx(0.96e12, rel=0.01)
        assert 2 * 32 * 128 * t * (t + 1) / 2 == pytest.approx(0.27e12, rel=0.02)
        assert chosen / (t * (t + 1) / 2) == pytest.approx(0.437, abs=5e-4)  # attn_selected_pct


def test_the_flash_kernels_floor_is_the_chosen_pairs_in_every_long_pass():
    """The cell's cycle: prefill of 7168, scoring of 8192 with the reference
    branch's one block, eight steps of one row forward, and the unfrozen
    block's backward; 2 x 64 x 512 operations a chosen pair, four products
    back for the forward's two, and the selection read once a row a layer."""
    model = types.SimpleNamespace(tcfg=PUBLISHED, n_layers=5, act_bytes=2, ref_layers=[4], epochs=1, lowest_trained=4)
    cycle = {"row_lengths": [(7168, 1024)] * 8, "steps": [{}] * 8}
    chosen = lambda t: 2048 * 2049 / 2 + (t - 2048) * 2048
    pair = 2 * 64 * 512
    fwd = {p["phase"]: p for p in costs.flash_fwd(model, cycle)}
    assert fwd["prefill"]["flops"] == 5 * 8 * pair * chosen(7168)
    assert fwd["score"]["flops"] == 5 * 8 * pair * chosen(8192) == 5 * fwd["score_reference"]["flops"]
    assert fwd["train_forward"]["flops"] == fwd["score"]["flops"]
    io = 2 * 8192 * 64 * (256 + 256 + 256 + 256)  # q, k, v read and o written, bf16
    assert fwd["score"]["bytes"] == 5 * 8 * (io + 8192 * 8192)
    (bwd,) = costs.flash_bwd(model, cycle)
    assert bwd["flops"] == 8 * 2 * pair * chosen(8192)
    generic = {p["phase"]: p for p in flops.flash_fwd(model, cycle)}
    assert fwd["score"]["flops"] / generic["score"]["flops"] == pytest.approx(0.43748, abs=1e-5)
    # a pass no longer than index_topk keeps every causal pair and reads no selection
    short = {p["phase"]: p for p in costs.flash_fwd(model, {"row_lengths": [(1000, 24)], "steps": [{}]})}
    assert short == {p["phase"]: p for p in flops.flash_fwd(model, {"row_lengths": [(1000, 24)], "steps": [{}]})}
    family = types.SimpleNamespace(family=flops.family_module("glm_moe_dsa"))
    assert flops.kernel_costs("flash_fwd", family) is costs.flash_fwd and flops.kernel_costs("flash_bwd", family) is costs.flash_bwd


def test_a_model_without_a_selection_gets_the_generic_walk():
    plain = types.SimpleNamespace(**{**vars(PUBLISHED), "index_topk": 0})
    tree = layer_tree(full=False)
    assert costs.layer_forward(plain, 1, tree, 640, {"moe/held_frac": 1.0}) == flops.generic_layer_forward(
        plain, 1, tree, 640, {"moe/held_frac": 1.0})


@pytest.mark.parametrize("name,reducer,wants", [
    ("sparse_decode_roofline", "trace_op_roofline", {"costs": "sparse_decode"}),
    ("sparse_decode_device_ms", "trace_op_sum", {}),
    ("index_select_device_ms", "trace_op_sum", {}),
    ("index_cache_gib", "stat_mean", {"key": "rollout/index_cache_bytes"}),
    ("attn_selected_pct", "stat_median", {"key": "learn/attn_selected_frac", "scale": 100.0}),
])
def test_the_metric_files_name_what_the_harness_finds(name, reducer, wants):
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "layer_metrics", f"{name}.json")) as f:
        spec = json.load(f)
    assert spec["reducer"] == reducer and all(spec[k] == v for k, v in wants.items())
    with open(os.path.join(os.path.dirname(here), "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    assert entry["workloads"] == ["glm52_ppo_ctx8k"]
    assert all(entry[k] == spec[k] for k in ("unit", "better", "source", "layer", "moves"))
    model = types.SimpleNamespace(family=flops.family_module("glm_moe_dsa"))
    assert flops.kernel_costs("sparse_decode", model) is costs.sparse_decode
    assert model.family.layer_forward is costs.layer_forward
