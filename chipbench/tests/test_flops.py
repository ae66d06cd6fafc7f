"""The compute yardsticks, checked without a chip.

    python3 -m pytest chipbench/tests -q        # from the root of the repository, JAX_PLATFORMS=cpu

(a) for each configuration's ``toy`` model the walk's forward count equals
    the sum of ``2MNK`` over the ``dot_general``s of the plain reference's
    jaxpr on one unpadded row, after the stated factors where the reference
    multiplies more than is required;
(b) a synthetic tree (a leading dense layer, later layers with 3-D kernels
    and 2 of 8 experts held, an ``attn`` with no ``q_proj``, LoRA leaves, a
    mask that freezes the base) counted by hand;
(c) a ``chipbench/costs/<family>.py`` is found and used;
(d) ``trace_op_roofline`` over a hand-made list of operations, both bounds,
    and over the recorded TPU trace of ``testdata/``.
"""

import importlib
import json
import math
import os
import sys
import types

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import flops, job, layers, trace_check  # noqa: E402

PEAK = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}


# ---------------------------------------------------------------------------
# (a) the walk against the plain references' own matmuls
# ---------------------------------------------------------------------------


def dot_flops(jaxpr, skip_scans_of=None) -> float:
    """Sum of ``2 * batch * M * N * K`` over every ``dot_general``, through
    calls, and through ``scan`` bodies times their length (a scan of
    ``skip_scans_of`` iterations is left out: a token-by-token recurrence)."""
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            batch = math.prod(lhs[d] for d in lb)
            k = math.prod(lhs[d] for d in lc)
            m = math.prod(s for d, s in enumerate(lhs) if d not in lc and d not in lb)
            n = math.prod(s for d, s in enumerate(rhs) if d not in rc and d not in rb)
            total += 2.0 * batch * m * n * k
            continue
        times = 1.0
        if eqn.primitive.name == "scan":
            if skip_scans_of is not None and eqn.params["length"] == skip_scans_of:
                continue
            times = float(eqn.params["length"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    total += times * dot_flops(inner, skip_scans_of)
    return total


def _toy_trainer(config_name):
    from trlx_tpu import perf

    cell = next(c for c in job.load_benchmark()["workloads"] if c["config"] == config_name)
    config_file = job.load_config(config_name, toy=True)
    traffic = job.load_json("traffic", cell["traffic"])
    cfg = job.build_config(config_file, traffic, 0, toy=True, ckpt_dir="/nonexistent")
    return perf._build_abstract_trainer(cfg), config_file


CONFIGS = [c["name"] for c in job.load_benchmark()["configs"]]
Q, R = 8, 16
HELD_FRAC = 0.25


@pytest.mark.parametrize("config_name", CONFIGS)
def test_forward_count_is_the_references_matmuls(config_name):
    from chipbench.checks import backbone_of

    trainer, config_file = _toy_trainer(config_name)
    model = flops.Model(trainer, config_file["family"])
    tcfg = trainer.tcfg
    t = Q + R
    stats = {"moe/held_frac": HELD_FRAC} if tcfg.experts_held != tcfg.num_experts else {}

    # what the plain reference multiplies, from the walk's own parts and the
    # stated factors: every held expert for every token (not k * f of them),
    # the full attention square in blocks of its query rows (not the causal
    # or window pairs), no conv and no chunked scan (its recurrence runs
    # token by token, left out of both sides)
    q_block = getattr(importlib.import_module(f"chipbench.reference.{config_file['family']}"),
                      "Q_BLOCK", t)
    square = float(-(-t // q_block) * q_block * t)
    expected = walked = 0.0
    for i in range(model.n_layers):
        cost = model.layer(i, t, stats)
        walked += sum(cost["matmuls"].values()) + cost["mix"]
        shapes = dict(flops._leaves(model.layers[i]))
        for path, value in cost["matmuls"].items():
            if len(shapes[path]) == 3:
                value *= shapes[path][0] / (tcfg.num_experts_per_tok * stats.get("moe/held_frac", 1.0))
            elif path[-1] == "conv_weight":
                value = 0.0
            expected += value
        expected += flops.attention_mix(tcfg, i, t) * square / flops.pairs(t, tcfg.layer_layout(i).window)
    head = sum(2.0 * a * b * R for a, b in model.head.values())
    value_head = sum(2.0 * a * b * R for a, b in model.value_head.values())
    expected += head

    counted = flops.row_flops(model, Q, R, stats)
    assert counted["forward"] == pytest.approx(walked + head + value_head, rel=1e-12)

    ref = importlib.import_module(f"chipbench.reference.{config_file['family']}")
    params = backbone_of(trainer.state.params)
    ids = jnp.zeros((1, t), jnp.int32)
    mask = jnp.ones((1, t), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, i, m: ref.logits(p, config_file["published"], i, m, (Q, t)))(params, ids, mask)
    in_reference = dot_flops(jaxpr.jaxpr, skip_scans_of=t if tcfg.mixer != "none" else None)
    assert in_reference == pytest.approx(expected, rel=0.02), (in_reference, expected)


def test_every_trained_cell_pays_three_passes_where_everything_trains():
    """GRPO's policy has no ``backbone`` key and every leaf trains whatever
    ``num_layers_unfrozen`` says: weight and activation gradients in every
    layer, which the count before PR 39 charged to two of four."""
    trainer, config_file = _toy_trainer("mistral-7b-l4")
    model = flops.Model(trainer, config_file["family"])
    assert trainer.num_layers_unfrozen == 2 and model.lowest_trained == -1
    c = flops.row_flops(model, Q, R, {})
    per_layer_mix = sum(flops.attention_mix(trainer.tcfg, i, Q + R) for i in range(model.n_layers))
    assert c["weight_grads"] == pytest.approx(c["forward"] - per_layer_mix, rel=1e-12)
    assert c["act_grads"] == pytest.approx(c["forward"] + per_layer_mix, rel=1e-12)


@pytest.mark.parametrize("config_name", ["mistral-7b-l4", "gptj-6b-l4"])
def test_backward_count_is_the_gradients_own_matmuls(config_name):
    """Where every layer trains (the toy models: GRPO trains all, the toy
    GPT-J has as many layers as are unfrozen) forward + weight gradients +
    activation gradients equal the ``dot_general``s of ``value_and_grad`` of
    the trainer's own loss, exactly, after the stated factors: the CPU's
    attention multiplies the full square where the causal pairs are
    required, and the program runs the value head on every position where
    the response positions are."""
    from trlx_tpu import perf

    trainer, config_file = _toy_trainer(config_name)
    model = flops.Model(trainer, config_file["family"])
    assert model.lowest_trained == -1 and all(all(m.values()) for m in model.layer_masks)
    rows, t = 2, Q + R
    batch = perf._train_batch_sds(type(trainer).__name__.lower(), rows, Q, R)
    rng = jax.ShapeDtypeStruct(trainer.state.rng.shape, trainer.state.rng.dtype)
    jaxpr = jax.make_jaxpr(lambda p, b, r: jax.value_and_grad(trainer.loss_fn, has_aux=True)(p, b, r))(
        trainer.state.params, batch, rng)
    square = sum(flops.attention_mix(trainer.tcfg, i, t)
                 * (t * t / flops.pairs(t, trainer.tcfg.layer_layout(i).window) - 1.0)
                 for i in range(model.n_layers))
    value_head_elsewhere = sum(2.0 * a * b * (t - R) for a, b in model.value_head.values())
    want = rows * (flops.row_flops(model, Q, R, {})["total"] + 3.0 * (square + value_head_elsewhere))
    assert dot_flops(jaxpr.jaxpr) == pytest.approx(want, rel=1e-9)


# ---------------------------------------------------------------------------
# (b) a tree this file has never seen, counted by hand
# ---------------------------------------------------------------------------

H, LAT, HEADS, DQK, DV, FF, FE, E_ROUTER, E_HELD, K, RANK = 32, 8, 4, 6, 4, 64, 16, 8, 2, 2, 2


def _sds(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


def _attn(lora):
    """Latent attention: a down- and an up-projection for q and for k/v, no
    leaf called ``q_proj``; LoRA on the two up-projections."""
    up = {"kernel": _sds(LAT, HEADS * DQK)}
    kv = {"kernel": _sds(LAT, HEADS * (DQK + DV))}
    if lora:
        up.update(lora_a=_sds(LAT, RANK), lora_b=_sds(RANK, HEADS * DQK))
        kv.update(lora_a=_sds(LAT, RANK), lora_b=_sds(RANK, HEADS * (DQK + DV)))
    return {"q_a": {"kernel": _sds(H, LAT)}, "q_b": up, "kv_a": {"kernel": _sds(H, LAT)},
            "kv_b": kv, "o_proj": {"kernel": _sds(HEADS * DV, H)},
            "q_a_norm": {"scale": _sds(LAT)}}


def _synthetic_trainer(ppo_epochs=1):
    dense = {"attn": _attn(True), "ln": {"scale": _sds(H)},
             "mlp": {"up_proj": {"kernel": _sds(H, FF)}, "down_proj": {"kernel": _sds(FF, H)}}}

    def sparse():
        return {"attn": _attn(True), "ln": {"scale": _sds(H)},
                "mlp": {"router": {"kernel": _sds(H, E_ROUTER)},
                        "shared_up": {"kernel": _sds(H, FE)}, "shared_down": {"kernel": _sds(FE, H)},
                        "w_up": _sds(E_HELD, H, FE), "w_down": _sds(E_HELD, FE, H)}}

    backbone = {"wte": {"embedding": _sds(100, H)}, "h_0": dense, "h_1": sparse(), "h_2": sparse(),
                "ln_f": {"scale": _sds(H)}, "lm_head": {"kernel": _sds(H, 100)}}
    params = {"backbone": backbone, "v_head": {"in_proj": {"kernel": _sds(H, 2 * H)},
                                               "out_proj": {"kernel": _sds(2 * H, 1)}}}
    # the base frozen; adapters of the LAST block and the value head train
    mask = jax.tree_util.tree_map_with_path(
        lambda path, _: (path[0].key == "v_head")
        or (path[1].key == "h_2" and path[-1].key.startswith("lora_")), params)
    layouts = [types.SimpleNamespace(window=None), types.SimpleNamespace(window=5),
               types.SimpleNamespace(window=None)]
    tcfg = types.SimpleNamespace(
        num_layers=3, num_heads=HEADS, kv_heads=HEADS, dims_per_head=DQK, v_head_dim=DV,
        num_experts_per_tok=K, mixer="none", dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        layer_layout=lambda i: layouts[i])
    return types.SimpleNamespace(
        state=types.SimpleNamespace(params=params), param_mask=mask, tcfg=tcfg,
        ref_params={"h_2": backbone["h_2"], "lm_head": backbone["lm_head"]},
        config=types.SimpleNamespace(method=types.SimpleNamespace(ppo_epochs=ppo_epochs)))


def _hand_count(q, r, f):
    """The synthetic tree by hand, one row."""
    t = q + r
    attn_base = H * LAT + LAT * HEADS * DQK + H * LAT + LAT * HEADS * (DQK + DV) + HEADS * DV * H
    lora = LAT * RANK + RANK * HEADS * DQK + LAT * RANK + RANK * HEADS * (DQK + DV)
    dense_mlp = 2 * H * FF
    sparse_mlp = H * E_ROUTER + 2 * H * FE + 2 * H * FE * K * f  # router, shared, k * f held experts
    causal = t * (t + 1) / 2
    windowed = 5 * 6 / 2 + (t - 5) * 5
    mix = [2 * HEADS * (DQK + DV) * p for p in (causal, windowed, causal)]
    matmul = [2 * t * (attn_base + lora + m) for m in (dense_mlp, sparse_mlp, sparse_mlp)]
    forward = sum(matmul) + sum(mix) + 2 * r * (H * 100) + 2 * r * (H * 2 * H + 2 * H)
    # only h_2's adapters and the value head train: weight gradients for
    # those, activation gradients through h_2 alone and through both heads
    weight = 2 * t * lora + 2 * r * (H * 2 * H + 2 * H)
    act = matmul[2] + 2 * mix[2] + 2 * r * (H * 100) + 2 * r * (H * 2 * H + 2 * H)
    return forward, weight, act


def test_synthetic_tree_by_hand():
    trainer = _synthetic_trainer()
    model = flops.Model(trainer)
    assert model.lowest_trained == 2 and model.ref_layers == [2]
    forward, weight, act = _hand_count(7, 9, 0.25)
    c = flops.row_flops(model, 7, 9, {"moe/held_frac": 0.25})
    assert c["forward"] == pytest.approx(forward, rel=1e-12)
    assert c["weight_grads"] == pytest.approx(weight, rel=1e-12)
    assert c["act_grads"] == pytest.approx(act, rel=1e-12)


def test_cycle_reads_held_frac_from_its_own_steps_and_counts_epochs():
    trainer = _synthetic_trainer(ppo_epochs=3)
    cycle = {"row_lengths": [(7, 9), (3, 9)],
             "steps": [{"moe/held_frac": 0.2}, {"moe/held_frac": 0.25}, {"moe/held_frac": 0.5}]}
    want = 3 * sum(sum(_hand_count(q, r, 0.25)) for q, r in cycle["row_lengths"])
    assert flops.learn_flops_of_cycle(trainer, cycle) == pytest.approx(want, rel=1e-12)


def test_wte_training_makes_every_block_pay_activation_gradients():
    trainer = _synthetic_trainer()
    trainer.param_mask["backbone"]["wte"]["embedding"] = True
    model = flops.Model(trainer)
    assert model.lowest_trained == -1
    base = flops.row_flops(flops.Model(_synthetic_trainer()), 7, 9, {"moe/held_frac": 0.25})
    c = flops.row_flops(model, 7, 9, {"moe/held_frac": 0.25})
    assert c["weight_grads"] == base["weight_grads"]  # an embedding's gradient is no matmul
    stats = {"moe/held_frac": 0.25}
    lower = [model.layer(i, 16, stats) for i in (0, 1)]
    assert c["act_grads"] == pytest.approx(
        base["act_grads"] + sum(sum(x["matmuls"].values()) + 2 * x["mix"] for x in lower))


def test_pairs_window_and_causal():
    assert flops.pairs(4, None) == 10 and flops.pairs(4, 8) == 10
    assert flops.pairs(6, 2) == sum(min(j + 1, 2) for j in range(6))
    assert flops.pairs(8192, 4096) == sum(min(j + 1, 4096) for j in range(8192))


def test_unreadable_tree_leaves_the_metric_out_with_a_reason(capsys):
    trainer = _synthetic_trainer()
    del trainer.state.params["backbone"]["h_1"]
    h = types.SimpleNamespace(trainer=trainer, config_file={"family": None}, cycles=[], flops_model=None)
    spec = {"name": "learn_mfu_pct", "reducer": "required_flops_share"}
    assert layers.reduce_one(spec, h, None, PEAK, 1) is None
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["metric_left_out"] == "learn_mfu_pct" and "h_1" in said["reason"]


# ---------------------------------------------------------------------------
# (c) a family brings its own counts as a new file
# ---------------------------------------------------------------------------

STUB = '''
def layer_forward(tcfg, i, layer_tree, t, stats):
    return {"matmuls": {("attn", "q_b", "lora_a"): 1000.0 * t, ("mlp", "nowhere"): 10.0 * t},
            "mix": 1.0 * t * (i + 1)}

def flash_fwd(model, cycle):
    return [{"phase": "all", "flops": 4e9, "bytes": 8e3}, {"phase": "bytes", "flops": 2e3, "bytes": 16e6}]
'''


@pytest.fixture
def stub_family(tmp_path, monkeypatch):
    import chipbench.costs

    (tmp_path / "stubfam.py").write_text(STUB)
    monkeypatch.setattr(chipbench.costs, "__path__", list(chipbench.costs.__path__) + [str(tmp_path)])
    yield "stubfam"
    sys.modules.pop("chipbench.costs.stubfam", None)


def test_a_familys_costs_file_is_found_and_used(stub_family):
    trainer = _synthetic_trainer()
    assert flops.family_module("no_such_family") is None
    model = flops.Model(trainer, stub_family)
    t, r = 16, 9
    c = flops.row_flops(model, 7, r, {})
    heads = 2 * r * (H * 100) + 2 * r * (H * 2 * H + 2 * H)
    assert c["forward"] == pytest.approx(3 * 1010.0 * t + t * (1 + 2 + 3) + heads)
    # the mask still decides: lora_a trains in h_2 alone, "nowhere" nowhere
    assert c["weight_grads"] == pytest.approx(1000.0 * t + 2 * r * (H * 2 * H + 2 * H))
    assert c["act_grads"] == pytest.approx(1010.0 * t + 2 * 3 * t + heads)
    assert flops.kernel_costs("flash_fwd", model)(model, {})[0]["phase"] == "all"
    assert flops.kernel_costs("flash_bwd", model) is flops.flash_bwd  # absent there: this file's


# ---------------------------------------------------------------------------
# (d) the roofline reducer
# ---------------------------------------------------------------------------


def _harness(model, cycle=None):
    return types.SimpleNamespace(trainer=None, config_file={"family": None}, flops_model=model,
                                 cycles=[cycle or {"row_lengths": [], "steps": []}])


def test_trace_op_roofline_both_bounds(stub_family, capsys):
    model = flops.Model(_synthetic_trainer(), stub_family)
    ops = {"/device:TPU:0": [("%flash_attention_fwd.3 = bf16[1] custom-call()", 0.0, 30e-6),
                             ("%flash_attention_fwd.4 = bf16[1] custom-call()", 1.0, 50e-6),
                             ("%flash_attention_bwd.3 = bf16[1] custom-call()", 2.0, 1.0),
                             ("%fusion.9 = bf16[1] fusion()", 3.0, 1.0)]}
    tr = {"ops": ops, "modules": {}, "spans": [], "window": (0.0, 4.0)}
    spec = {"name": "flash_fwd_roofline", "reducer": "trace_op_roofline",
            "pattern": "^%flash_attention_fwd[.0-9]* = ", "costs": "flash_fwd"}
    # phase "all" is bound by operations (4e9 / 200e12 = 20 us against 8e3 / 800e9 = 0.01 us),
    # phase "bytes" by bytes (16e6 / 800e9 = 20 us against 0.00001 us): 40 us over 80 us
    assert layers.reduce_one(spec, _harness(model), tr, PEAK, 1) == pytest.approx(50.0)
    assert "floor_s" in capsys.readouterr().out
    # nothing matched, no trace, no peak (the CPU walk): nothing, never 0
    assert layers.reduce_one(dict(spec, pattern="^%nothing"), _harness(model), tr, PEAK, 1) is None
    assert layers.reduce_one(spec, _harness(model), None, PEAK, 1) is None
    assert layers.reduce_one(spec, _harness(model), tr, None, 1) is None
    with pytest.raises(ValueError):
        layers.reduce_one(dict(spec, costs="no_such_kernel"), _harness(model), tr, PEAK, 1)


def test_trace_op_roofline_on_the_recorded_tpu_trace():
    """``testdata/tpu_trace_small.json``: one forward and backward of a
    two-layer GPT-J-width stack on 4 x 512 tokens, recorded on a v5e. The
    generic flash costs of that step over the recorded Mosaic calls' time:
    a share of a roofline, so above 0 and under 100."""
    ops, modules, spans = trace_check.load_recorded()
    tr = {"ops": ops, "modules": modules, "spans": spans, "window": (0.0, 1.0)}
    trainer = _synthetic_trainer()
    trainer.tcfg = types.SimpleNamespace(
        num_layers=2, num_heads=16, kv_heads=16, dims_per_head=256, num_experts_per_tok=0,
        mixer="none", dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        layer_layout=lambda i: types.SimpleNamespace(window=None))
    trainer.param_mask = jax.tree_util.tree_map(lambda _: True, trainer.state.params)
    model = flops.Model(trainer)
    cycle = {"row_lengths": [(0, 512)] * 4, "steps": [{}]}
    spent = sum(d for n, _, d in next(iter(ops.values())) if "tpu_custom_call" in n)
    phases = [p for p in flops.flash_fwd(model, cycle) if p["phase"] == "train_forward"]
    phases += flops.flash_bwd(model, cycle)
    # forward 2 and backward 4 products of 16 heads x 256 over the causal pairs, 2 layers, 4 rows
    want = 2 * 4 * 6 * 16 * 256 * (512 * 513 / 2) * 2
    assert sum(p["flops"] for p in phases) == pytest.approx(want)

    stub = types.ModuleType("chipbench.costs.recorded")
    stub.step = lambda model, cycle: phases
    model.family = stub
    spec = {"name": "flash_roofline", "reducer": "trace_op_roofline", "costs": "step",
            "pattern": 'custom_call_target="tpu_custom_call"'}
    share = layers.reduce_one(spec, _harness(model, cycle), tr,
                              {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, 1)
    assert share == pytest.approx(100.0 * flops.floor_seconds(
        phases, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, 1) / spent)
    assert 0.0 < share < 100.0


def test_moe_gmm_phases_and_lower_bound_of_experts_hit():
    trainer = _synthetic_trainer()
    trainer.param_mask = jax.tree_util.tree_map(lambda _: True, trainer.state.params)
    model = flops.Model(trainer)
    cycle = {"row_lengths": [(4, 6)] * 2, "steps": [{"moe/held_frac": 0.25}] * 2}
    phases = {p["phase"]: p for p in flops.moe_gmm(model, cycle)}
    assert set(phases) == {"prefill", "score", "score_reference", "train_forward",
                           "train_backward", "decode"}
    per_token = 2 * 2 * (2.0 * H * FE) * K * 0.25          # two sparse layers, two 3-D leaves each
    assert phases["prefill"]["flops"] == pytest.approx(per_token * 8)
    assert phases["score_reference"]["flops"] == pytest.approx(per_token / 2 * 20)  # h_2 alone
    assert phases["train_backward"]["flops"] == pytest.approx(2 * phases["train_forward"]["flops"])
    assert phases["decode"]["flops"] == pytest.approx(per_token * 2 * 5)
    # ceil(k * f) = 1 of the 2 held experts a call, 5 decode steps, 4 leaves, bf16
    rows = 2 * (H + FE) * K * 0.25 * 10 * 4
    assert phases["decode"]["bytes"] == pytest.approx(rows + 5 * 4 * 2 * 1 * H * FE)
    assert flops.moe_gmm(flops.Model(_toy_trainer("mistral-7b-l4")[0]), cycle) == []
