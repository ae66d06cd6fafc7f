"""LFM2-8B-A1B (``model_type`` ``lfm2_moe``): gated short convolution layers
(``ShortConvMixer``: ``C * conv3(B * x)`` between two projections, the conv's
last two input rows the layer's whole cache) beside GQA attention layers with
a per-head QK-norm, two leading dense layers, then sigmoid-routed held experts
under a selection bias, the head tied to the embedding, against the plain
float32 reference the benchmark keeps (``chipbench/reference/lfm2_moe.py``) at
toy widths on the CPU.

``builtin:lfm2-test``: conv, conv (both dense), attention, conv, attention,
conv; hidden 64, GQA 4/2 of 16, dense 128, 8 experts of 32 top-2, 3 taps.
"""

import dataclasses
import hashlib
import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import trlx_tpu.models.transformer as tf
from chipbench.reference import lfm2_moe as ref
from trlx_tpu.models.transformer import CausalTransformer, config_from_spec, make_kv_cache
from trlx_tpu.ops.cache_layout import CONV, KINDS, KV, PATHS, cache_bytes, cache_slots, describe, refuse
from trlx_tpu.ops.sampling import GenerationConfig

TOL = 2e-5  # relative L2 of float32 logits: what is left is the order of summation

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
CFG = config_from_spec("builtin:lfm2-test", attention_impl="xla", **F32)
HELD = dataclasses.replace(CFG, moe_experts_held=2, moe_first_expert=2)  # one chip's share: experts 2 and 3 of the router's 8
MODEL = CausalTransformer(CFG)
CONV_LAYERS, ATTN_LAYERS = (0, 1, 3, 5), (2, 4)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def dims_of(cfg):
    """The published keys the reference reads, from the config under test."""
    return dict(
        hidden_size=cfg.hidden_size, num_hidden_layers=cfg.num_layers, norm_eps=cfg.layer_norm_epsilon,
        num_attention_heads=cfg.num_heads, num_key_value_heads=cfg.kv_heads, rope_theta=cfg.rope_theta,
        layer_types=["conv" if l.mixer == "conv" else "full_attention" for l in cfg.layer_layouts],
        num_dense_layers=cfg.first_k_dense, conv_L_cache=cfg.conv_L_cache, num_experts=cfg.experts_held,
        num_experts_per_tok=cfg.num_experts_per_tok, routed_scaling_factor=cfg.routed_scaling_factor,
        moe_first_expert_held=cfg.moe_first_expert,
    )


DIMS = dims_of(CFG)


def seeded(params, seed=0):
    """Weights at which every mechanism shows: matrices of unit gain, norm
    scales scattered about 1, a selection bias as large as the scores' spread."""

    def leaf(path, x):
        name = jax.tree_util.keystr(path)
        rs = np.random.RandomState(int(hashlib.sha256(f"{seed}{name}".encode()).hexdigest()[:8], 16))
        if name.endswith("['scale']"):
            return jnp.asarray(1.0 + 0.3 * rs.randn(*x.shape), x.dtype)
        if name.endswith("['router_bias']"):
            return jnp.asarray(0.2 * rs.randn(*x.shape), x.dtype)
        if name.endswith("['kernel']") or x.ndim == 3:
            return jnp.asarray(rs.randn(*x.shape) / np.sqrt(x.shape[-2]), x.dtype)
        return x  # the embedding (std 1) and the taps (torch's Conv1d default) as drawn

    return jax.tree_util.tree_map_with_path(leaf, params)


def init(cfg=CFG, seed=0):
    return seeded(CausalTransformer(cfg).init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"], seed)


PARAMS = init()


def batch(T, pads, seed=1):
    rs = np.random.RandomState(seed)
    ids = jnp.asarray(rs.randint(0, 259, (len(pads), T)))
    mask = jnp.asarray(np.arange(T)[None, :] >= np.asarray(pads)[:, None], jnp.int32)
    return ids, mask


def rel(a, b, mask):
    m = np.asarray(mask)[..., None]
    return float(np.sqrt(np.sum(((np.asarray(a) - np.asarray(b)) * m) ** 2) / np.sum((np.asarray(b) * m) ** 2)))


# ---------------------------------------------------------------------------
# the whole forward against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("cfg", [CFG, HELD], ids=["all_experts", "experts_2_and_3"])
def test_logits_match_the_reference_on_left_padded_rows_of_unlike_length(cfg, impl):
    T = 40
    ids, mask = batch(T, [0, 5, 11])
    params = init(cfg, seed=3)
    model = CausalTransformer(dataclasses.replace(cfg, attention_impl=impl))
    got = model.apply({"params": params}, ids, attention_mask=mask)["logits"]
    want = ref.logits(params, dims_of(cfg), ids, mask, (0, T))
    assert got.shape == (3, T, 259) and "lm_head" not in params  # the head is the embedding
    assert rel(got, want, mask) < TOL


def test_a_rows_logits_do_not_depend_on_the_padding_in_front_of_it():
    """A padded slot feeds nothing into the conv window: zeros left of the
    row's first real token, as left of a row that starts at slot 0."""
    T, pad = 30, 13
    ids, mask = batch(T, [pad])
    padded = MODEL.apply({"params": PARAMS}, ids, attention_mask=mask)["logits"][0, pad:]
    alone = MODEL.apply({"params": PARAMS}, ids[:, pad:], attention_mask=mask[:, pad:])["logits"][0]
    assert float(jnp.abs(padded - alone).max()) < TOL * float(jnp.abs(alone).max())


@pytest.mark.parametrize("P", [1, 2, 17], ids=["prefill_of_one", "prefill_shorter_than_the_window", "prefill_17"])
def test_prefill_then_decode_through_the_conv_rows_and_kv_matches_the_references_full_forward(P):
    """The sampler's two programs: a span from slot 0 (a pass from the stored
    rows, zeros), then single tokens from the window's two rows and, on the
    attention layers, over K and V under static extents, against the
    reference's full forward; the row with the longest padding is still inside
    it when the shortest prefill ends."""
    T = 28
    ids, mask = batch(T, [0, 0, 0] if P < 3 else [0, 7, 16])
    want = ref.logits(PARAMS, DIMS, ids, mask, (0, T))
    cache = make_kv_cache(CFG, 3, T, jnp.float32)
    assert [set(layer) for layer in cache] == [{"conv"} if i in CONV_LAYERS else {"k", "v"} for i in range(6)]
    assert cache[0]["conv"].shape == (3, 2, 64) and cache[2]["k"].shape == (3, T, 2, 16)
    out = MODEL.apply({"params": PARAMS}, ids[:, :P], attention_mask=mask, cache=cache, cache_index=0)
    logits, cache = [out["logits"]], out["cache"]
    for t in range(P, T):
        out = MODEL.apply({"params": PARAMS}, ids[:, t : t + 1], attention_mask=mask, cache=cache, cache_index=t, kv_extents=(16, T))
        logits.append(out["logits"])
        cache = out["cache"]
    assert rel(jnp.concatenate(logits, axis=1), want, mask) < TOL
    assert set(cache[5]) == {"conv"} and cache[5]["conv"].shape == (3, 2, 64)


def test_the_cached_rows_are_the_gated_inputs_of_the_last_two_slots():
    """``g_{t-1}, g_{t-2}``: after a prefill of a left-padded row, and after a
    step, the layer's cache holds ``B * z`` of the last two slots, zeros where
    a slot is padding."""
    T = 9
    ids, mask = batch(T, [0, 8])
    cache = make_kv_cache(CFG, 2, T + 1, jnp.float32)
    slots = jnp.concatenate([mask, jnp.ones((2, 1), jnp.int32)], axis=1)
    out = MODEL.apply({"params": PARAMS}, ids, attention_mask=slots, cache=cache, cache_index=0)
    p = PARAMS["h_0"]
    u = ref._rms_norm(PARAMS["wte"]["embedding"][ids], p["ln_attn"]["scale"], 1e-5)
    b, _, z = jnp.split(u @ p["attn"]["in_proj"]["kernel"], 3, axis=-1)
    g = b * z * mask[..., None]
    np.testing.assert_allclose(out["cache"][0]["conv"], g[:, -2:], atol=1e-5)
    assert float(jnp.abs(out["cache"][0]["conv"][1, 0]).max()) == 0.0  # slot 7 of the second row is padding


@pytest.mark.parametrize("fault", ref.FAULTS + ref.PRECISION_CONTROLS)
def test_every_planted_fault_of_the_reference_is_caught(fault):
    """Each other reading of what the catalog row does not settle, planted in
    the reference, moves the float32 logits far past the agreement above."""
    T = 40
    ids, mask = batch(T, [0, 5, 11])
    got = MODEL.apply({"params": PARAMS}, ids, attention_mask=mask)["logits"]
    moved = rel(got, ref.logits(PARAMS, DIMS, ids, mask, (0, T), fault=fault), mask)
    assert moved > 5e-3, (fault, moved)


def test_the_renormalisation_adds_the_published_epsilon():
    """``sum + 1e-6``, a field: unobservable at the configuration's precision
    (``assumed``), so held here, on scores small enough to show it."""
    mlp = init(seed=5)["h_3"]["mlp"]
    v = jnp.ones((64,)) / 8.0  # a unit direction every token shares: logits of -14 + noise, sigmoid scores near 1e-6
    x = v + 0.05 * jax.random.normal(jax.random.PRNGKey(2), (2, 7, 64))
    mlp = dict(mlp, router={"kernel": 0.1 * mlp["router"]["kernel"] - 14.0 * v[:, None]})
    want = ref.moe_layer(mlp, x, 2, 1.0)
    got, _ = tf.MoEMLP(CFG).apply({"params": mlp}, x)
    older, _ = tf.MoEMLP(dataclasses.replace(CFG, moe_renormalize_eps=0.0)).apply({"params": mlp}, x)
    ones = np.ones((2, 7))
    assert rel(got, want, ones) < TOL and rel(older, want, ones) > 1e-2


def test_the_hydra_branch_replays_a_conv_layer_and_an_attention_layer():
    """``forward_branch`` over the last two blocks (an attention layer, then a
    conv layer from zeros) on the trunk's activations gives the full pass's
    logits, on left-padded rows."""
    T = 24
    ids, mask = batch(T, [0, 6])
    out = MODEL.apply({"params": PARAMS}, ids, attention_mask=mask, branch_layer=2)
    branch = MODEL.apply({"params": PARAMS}, out["branch_input"], 2, mask, method=MODEL.forward_branch)
    assert rel(branch["logits"], out["logits"], mask) < 1e-6


@pytest.mark.parametrize("cfg", [CFG, HELD], ids=["all_experts", "experts_2_and_3"])
def test_grpo_loss_and_gradients_of_one_train_step_match_the_references(cfg):
    """The GRPO objective on a 14-token query and a 16-token response, with
    respect to EVERY leaf (a GRPO job trains them all): through the conv
    mixers' gates and taps, the per-head norms, the router and the held
    experts, and the tied embedding as table and as head, against autodiff
    through the reference."""
    from trlx_tpu.data.default_configs import default_grpo_config
    from trlx_tpu.utils.stats import logprobs_of_labels

    method = default_grpo_config().method
    T, Q = 30, 14
    params, (ids, mask) = init(cfg, seed=7), batch(T, [0, 3, 9], seed=7)
    rs = np.random.RandomState(7)
    drift = jnp.asarray(0.3 * rs.randn(3, T - Q), jnp.float32)
    behind = jnp.asarray(-3.0 + 0.3 * rs.randn(3, T - Q), jnp.float32)
    adv = jnp.asarray([1.0, -0.5, 0.25], jnp.float32)

    def objective(logits_of):
        def f(p):
            lp = logprobs_of_labels(logits_of(p), ids[:, Q:])
            # old logprobs a fixed distance from the current ones, so that some ratios are clipped and some are not
            return method.loss(logprobs=lp, old_logprobs=jax.lax.stop_gradient(lp) + drift, ref_logprobs=behind,
                               advantages=adv, mask=mask[:, Q:])[0]
        return f

    model = CausalTransformer(cfg)
    loss, got = jax.value_and_grad(objective(lambda p: model.apply(
        {"params": p}, ids, attention_mask=mask, logits_span=(Q - 1, T - 1))["logits"]))(params)
    want_loss, want = jax.value_and_grad(objective(lambda p: ref.logits(p, dims_of(cfg), ids, mask, (Q - 1, T - 1))))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * abs(float(want_loss))
    nonzero = 0
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']"):  # selects, weighs nothing, takes no gradient
            assert float(jnp.abs(g).max()) == 0.0 and float(jnp.abs(w).max()) == 0.0
            continue
        err = float(jnp.linalg.norm(g - w) / jnp.maximum(jnp.linalg.norm(w), 1e-12))
        assert err < 1e-4, (name, err)  # (a leaf no held expert's rows reach has a zero gradient on both sides)
        nonzero += float(jnp.linalg.norm(w)) > 0
    assert nonzero >= 40


# ---------------------------------------------------------------------------
# the shares, the preset, the configuration file, the cache's description
# ---------------------------------------------------------------------------


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The guide's section 4: a layer that holds experts ``[first, first +
    held)`` of the router's width returns the part its own give; the four
    shares (8 experts in shares of 2; no shared expert to count once) are the
    uncut layer, program and reference alike."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 64))
    mlp = init(seed=4)["h_2"]["mlp"]
    ones = np.ones((2, 9))
    full, _ = tf.MoEMLP(CFG).apply({"params": mlp}, x)
    assert rel(full, ref.moe_layer(mlp, x, 2, 1.0), ones) < TOL
    total = 0.0
    for first in range(0, 8, 2):
        cut = dataclasses.replace(CFG, moe_experts_held=2, moe_first_expert=first)
        held = dict(mlp, **{k: mlp[k][first : first + 2] for k in ("w_gate", "w_up", "w_down")})
        share, _ = tf.MoEMLP(cut).apply({"params": held}, x)
        assert rel(share, ref.moe_layer(held, x, 2, 1.0, first=first), ones) < TOL
        total = total + share
    assert rel(total, full, ones) < TOL


def catalog_row():
    with open(CATALOG) as f:
        return next(row for row in map(json.loads, f) if row["name"] == "LFM2-8B-A1B")


def test_the_preset_holds_the_published_keys():
    big, published = config_from_spec("builtin:lfm2-8b-a1b"), catalog_row()["config"]
    assert tuple("conv" if l.mixer == "conv" else "full_attention" for l in big.layer_layouts) == tuple(published["layer_types"])
    assert [l.ffn for l in big.layer_layouts] == ["dense"] * published["num_dense_layers"] + ["moe"] * 22
    assert all(l.rotary and l.window is None for l in big.layer_layouts)
    fields = dict(
        hidden_size="hidden_size", intermediate_size="intermediate_size", moe_intermediate_size="moe_intermediate_size",
        num_attention_heads="num_heads", num_key_value_heads="num_kv_heads", num_hidden_layers="num_layers",
        num_experts="num_experts", num_experts_per_tok="num_experts_per_tok", norm_topk_prob="moe_renormalize",
        routed_scaling_factor="routed_scaling_factor", rope_theta="rope_theta", norm_eps="layer_norm_epsilon",
        vocab_size="vocab_size", max_position_embeddings="max_position_embeddings", conv_L_cache="conv_L_cache",
        conv_bias="conv_bias", model_type="model_type")
    for key, field in fields.items():
        assert getattr(big, field) == published[key], key
    assert big.moe_topk_method == "noaux_tc" and published["use_expert_bias"] is True
    assert (big.dims_per_head, big.qk_norm, big.moe_scoring, big.num_shared_experts) == (64, "head", "sigmoid", 0)
    assert big.tie_word_embeddings and big.moe_renormalize_eps == 1e-6 and big.moe_capacity_factor == 0
    assert hash(big) == hash(config_from_spec("builtin:lfm2-8b-a1b"))


@pytest.mark.parametrize("override,match", [
    (dict(mixer_layout=("conv", "window") * 3), "attention \\| lightning \\| kda \\| conv"),
    (dict(conv_L_cache=1), "conv_L_cache >= 2"),
    (dict(conv_bias=True), "no conv_bias"),
    (dict(mixer="mamba2"), "no second mixer"),
])
def test_the_config_refuses_a_layout_it_cannot_run(override, match):
    with pytest.raises(ValueError, match=match):
        config_from_spec("builtin:lfm2-test", **override)


def test_scan_layers_refuses_the_mixed_stack_by_name():
    with pytest.raises(NotImplementedError, match="scan_layers.*lfm2_moe"):
        CausalTransformer(config_from_spec("builtin:lfm2-test", scan_layers=True)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_the_cut_is_the_configuration_files_and_its_arithmetic_holds():
    from chipbench import job
    from trlx_tpu.data.configs import ModelConfig, ParallelConfig

    file, row = job.load_config("lfm2-8b-a1b-l10e8"), catalog_row()
    assert file["source"] == row["source_url"] and file["family"] == "lfm2_moe"
    for key, value in row["config"].items():  # every number of the catalog's config under the same key, but the reduced ones
        if key not in file["reduced"]:
            assert file["published"][key] == value, key
    reduced = ["layer_types", "num_experts", "num_hidden_layers", "vocab_size"]
    assert sorted(file["reduced"]) == reduced == sorted(next(
        c["reduced"] for c in job.load_benchmark()["configs"] if c["name"] == "lfm2-8b-a1b-l10e8"))
    assert file["published"]["layer_types"] == row["config"]["layer_types"][:10]
    assert (file["published"]["num_hidden_layers"], file["published"]["num_experts"], file["published"]["vocab_size"]) == (10, 8, 16384)
    model = file["job"]["model"]
    cut = config_from_spec(model["model_path"], **model["model_extra_kwargs"])
    assert tuple("conv" if l.mixer == "conv" else "full_attention" for l in cut.layer_layouts) == tuple(file["published"]["layer_types"])
    assert (cut.num_experts, cut.experts_held, cut.moe_first_expert) == (file["router_width"], 8, 0)
    cfg = types.SimpleNamespace(model=ModelConfig(**model), parallel=ParallelConfig(**file["job"]["parallel"]))
    job.check_published_widths(cfg, file)
    shapes = jax.eval_shape(lambda: CausalTransformer(cut).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == 982_084_096  # the configuration file's table
    assert count(shapes["h_0"]["attn"]) == 16_783_360 and count(shapes["h_2"]["attn"]) == 10_485_888
    assert count(shapes["h_0"]["mlp"]) == 44_040_192 and count(shapes["h_2"]["mlp"]) == 88_080_384 + 65_536 + 32
    cache = jax.eval_shape(lambda: make_kv_cache(cut, 128, 1152))
    assert cache[0]["conv"].shape == (128, 2, 2048) and cache[2]["k"].shape == (128, 1152, 4, 128)  # two KV heads of 64 a row (lane_heads)
    held = cache_bytes(cache, 1152)
    assert held[CONV] == 8 * 2**20 and held[KV] == 2 * 2 * 128 * 1152 * 8 * 64 * 2 and sum(held.values()) == held[CONV] + held[KV]
    traffic = job.load_json("traffic", "grpo_reason_r128")
    assert traffic["job"]["method"] == dict(num_rollouts=128, chunk_size=128, group_size=8, ppo_epochs=1)
    assert (traffic["prompts_per_cycle"], traffic["prompt_length"]["tokens"], traffic["max_new_tokens"]) == (16, 128, 1024)
    toy = job.load_config("lfm2-8b-a1b-l10e8", toy=True)
    toy_model = dict(model, **toy["toy"]["model"])
    toy_cfg = config_from_spec(toy_model["model_path"], **toy_model["model_extra_kwargs"])
    for key, value in dims_of(toy_cfg).items():
        if key != "moe_first_expert_held":
            assert toy["published"][key] == value, key


def test_a_conv_layers_cache_has_a_kind_of_its_own():
    """``{"conv": [B, 2, hidden]}`` alone: described under ``conv`` (a conv
    leaf beside ``ssm`` or ``state`` stays its neighbour's kind), no slot
    axis, counted apart from K and V."""
    cache = jax.eval_shape(lambda: make_kv_cache(CFG, 3, 20))
    assert [(h.name, h.kind, h.slots, h.bytes) for h in describe(cache[0])] == [("conv", CONV, None, 3 * 2 * 64 * 4)]
    assert cache_slots(cache[0]) is None and cache_slots(cache[2]) == 20
    held = cache_bytes(cache, 20)
    assert held[CONV] == 4 * 3 * 2 * 64 * 4 and held[KV] == 2 * 2 * 3 * 20 * 2 * 16 * 4 and set(held) == {CONV, KV}
    assert "layer's whole cache" in KINDS[CONV]
    for family, kind in (("falconh1", "recurrent"), ("kimi-linear", "linear")):
        other = jax.eval_shape(lambda: make_kv_cache(config_from_spec(f"builtin:{family}-test"), 1, 8))
        assert {h.kind for h in describe(other) if h.name == "conv"} == {kind}


@pytest.mark.parametrize("path", PATHS)
def test_kv_only_paths_refuse_a_conv_layer_by_name(path):
    cache = jax.eval_shape(lambda: make_kv_cache(CFG, 1, 8))
    with pytest.raises(NotImplementedError, match=rf"^{path} .*a short convolution's last input rows as the layer's whole cache \(leaves \['conv'\]\): .*B7[bc]\)"):
        refuse(cache, path, 8)
    refuse([layer for i, layer in enumerate(cache) if i in ATTN_LAYERS], path, 8)  # the K and V layers alone are held


@pytest.mark.parametrize("way", ["import", "export"])
def test_hf_interop_says_there_is_no_converter(way):
    from trlx_tpu.models.hf_interop import UnsupportedHFExport, config_from_hf, hf_config_from_transformer

    if way == "import":
        with pytest.raises(ValueError, match="lfm2_moe.*no HF checkpoint conversion.*B7"):
            config_from_hf(types.SimpleNamespace(model_type="lfm2_moe"))
    else:
        with pytest.raises(UnsupportedHFExport, match="lfm2_moe.*no HF checkpoint conversion"):
            hf_config_from_transformer(CFG)


# ---------------------------------------------------------------------------
# required work (chipbench/costs/lfm2_moe.py)
# ---------------------------------------------------------------------------


def test_the_required_work_counts_the_conv_and_the_gates():
    from chipbench import flops
    from chipbench.costs import lfm2_moe as costs

    shapes = jax.eval_shape(lambda: MODEL.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    t = 50
    dense_conv = costs.layer_forward(CFG, 0, shapes["h_0"], t, {})
    expert_attn = costs.layer_forward(CFG, 2, shapes["h_2"], t, {"moe/held_frac": 0.5})
    expert_conv = costs.layer_forward(CFG, 3, shapes["h_3"], t, {"moe/held_frac": 0.5})
    assert dense_conv["mix"] == expert_conv["mix"] == 2 * 64 * t  # two gates a channel a token
    assert dense_conv["matmuls"][("attn", "conv_weight")] == 2 * 3 * 64 * t
    assert dense_conv["matmuls"][("attn", "in_proj", "kernel")] == 2 * 64 * 192 * t
    assert expert_attn["mix"] == 2 * 4 * (16 + 16) * flops.pairs(t, None) and ("attn", "conv_weight") not in expert_attn["matmuls"]
    assert expert_conv["matmuls"][("mlp", "w_up")] == 2 * 64 * 32 * 2 * 0.5 * t  # k = 2, half of them held
    model = types.SimpleNamespace(tcfg=CFG, n_layers=6, lowest_trained=-1, ref_layers=[4, 5], epochs=1, act_bytes=2)
    cycle = {"row_lengths": [(30, 10)] * 4, "steps": [{}] * 2}
    assert [p["phase"] for p in costs.flash_fwd(model, cycle)] == ["prefill", "score", "score_reference", "train_forward"]
    assert costs.flash_fwd(model, cycle)[1]["flops"] == 2 * 4 * expert_attn["mix"] / flops.pairs(t, None) * flops.pairs(40, None)
    assert costs.flash_bwd(model, cycle)[0]["flops"] == 2 * costs.flash_fwd(model, cycle)[3]["flops"]


NEW_METRICS = ("conv_cache_gib", "short_conv_pass_device_ms", "short_conv_step_device_ms")
APPENDED_TO = ("kv_cache_gib", "moe_held_pct", "moe_held_imbalance", "moe_share_gmm_device_ms", "moe_gmm_roofline")


@pytest.mark.parametrize("name", NEW_METRICS + APPENDED_TO)
def test_the_cells_metrics_are_declared_and_their_files_name_what_the_harness_finds(name):
    """Each new metric lists the new cell alone and agrees with its file; each
    accepted metric the cell joins lists it (a later cell is appended behind
    it), and its file is the accepted one (a reducer the harness has, a key the program
    logs or a pattern that compiles, a cost function the family's file or
    ``flops.py`` brings)."""
    from chipbench import flops, job, layers

    cell = "lfm2_8b_grpo_reason_r128"
    entry = next(m for m in job.load_benchmark()["per_layer"] if m["name"] == name)
    spec = layers.metric_files()[name]
    assert all(entry[k] == spec[k] for k in ("unit", "better", "source", "layer", "moves"))
    assert cell in entry["workloads"] and (entry["workloads"] == [cell]) == (name in NEW_METRICS)
    if "pattern" in spec:
        re.compile(spec["pattern"])
        assert "PATTERN" not in spec["pattern"] + spec["reads"] and "TODO" not in spec["reads"]
    if "costs" in spec:
        model = types.SimpleNamespace(family=flops.family_module("lfm2_moe"))
        assert callable(flops.kernel_costs(spec["costs"], model))
    if "key" in spec:
        trainer_dir = os.path.join(os.path.dirname(tf.__file__), "..", "trainer")
        source = open(os.path.join(trainer_dir, "base.py")).read() + open(os.path.join(trainer_dir, "ppo.py")).read()
        assert f'"{spec["key"]}"' in source


def test_forward_count_is_the_references_matmuls_in_both_kinds_of_layer(monkeypatch):
    """``chipbench/tests/test_flops.py``'s check of the forward count, for a
    stack whose layers are not all attention (that file charges every layer a
    score square; tier 1 carries its case for this configuration as an
    expected failure): the family's ``layer_forward`` against the products in
    the reference's own jaxpr at the toy widths. The reference multiplies every
    projection, every HELD expert for every token, an attention layer's full
    score square; the conv and the gates are element-wise there."""
    import trlx_tpu.trainer.base as base
    from chipbench import flops
    from chipbench.checks import backbone_of
    from chipbench.costs import lfm2_moe as costs
    from chipbench.tests.test_flops import HELD_FRAC, Q, R, _toy_trainer, dot_flops
    from trlx_tpu.parallel import make_mesh

    monkeypatch.setattr(base, "make_mesh", lambda parallel: make_mesh(parallel, devices=jax.devices()[:1]))
    trainer, config_file = _toy_trainer("lfm2-8b-a1b-l10e8")
    model, tcfg, t = flops.Model(trainer, "lfm2_moe"), trainer.tcfg, Q + R
    assert model.layer_forward is costs.layer_forward and list(model.head) == [("wte", "embedding")]  # the tied head is counted
    stats, expected = {"moe/held_frac": HELD_FRAC}, 0.0
    for i in range(model.n_layers):
        cost = model.layer(i, t, stats)
        shapes = dict(flops._leaves(model.layers[i]))
        for path, value in cost["matmuls"].items():
            if len(shapes[path]) == 3:  # every held expert on every token, not k x held_frac of them
                value *= shapes[path][0] / (tcfg.num_experts_per_tok * HELD_FRAC)
            expected += 0.0 if path[-1] == "conv_weight" else value
        if costs.is_conv(tcfg, i):
            assert cost["mix"] == 2 * 64 * t
        else:
            expected += cost["mix"] * (t * t) / flops.pairs(t, None)
    expected += sum(2.0 * a * b * R for a, b in model.head.values())
    params = backbone_of(trainer.state.params)
    ids, mask = jnp.zeros((1, t), jnp.int32), jnp.ones((1, t), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, i, m: ref.logits(p, config_file["published"], i, m, (Q, t)))(params, ids, mask)
    assert dot_flops(jaxpr.jaxpr) == pytest.approx(expected, rel=0.02)


# ---------------------------------------------------------------------------
# no existing program moves
# ---------------------------------------------------------------------------

RECORDED = os.path.join(os.path.dirname(__file__), "fixtures", "programs_before_lfm2.json")
RECORDED_FAMILIES = ("mistral", "gptj", "olmoe", "falconh1", "smallthinker", "pangu", "glm", "k-exaone", "minicpm-sala",
                     "kimi-linear", "dots3-note")  # the presets of every accepted configuration


def program_fingerprints(family):
    """sha256 of a toy preset's parameter tree, cache tree, and the jaxpr text
    of one train step (the gradient of a loss on the response's logits, with
    the hydra branch's input taken) and one decode step under two extents
    (float32, xla attention), on rows of 12 slots behind 3 pads
    (``tests/test_kimi_linear.py`` has the function's first form; the test
    takes ``clean_trace_state``)."""
    cfg = config_from_spec(f"builtin:{family}-test", attention_impl="xla", **F32)
    model = CausalTransformer(cfg)
    ids = jnp.zeros((2, 12), jnp.int32)
    mask = jnp.ones((2, 12), jnp.int32).at[0, :3].set(0)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids)["params"])
    cache = jax.eval_shape(lambda: make_kv_cache(cfg, 2, 16))
    slots = jnp.ones((2, 16), jnp.int32)

    def loss(p):
        out = model.apply({"params": p}, ids, attention_mask=mask, branch_layer=1, logits_span=(8, 12))
        return jnp.mean(out["logits"] ** 2)

    texts = {
        "params": str(jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), params)),
        "cache": str(jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), cache)),
        "train": str(jax.make_jaxpr(jax.grad(loss))(params)),
        "decode": str(jax.make_jaxpr(
            lambda p, c: model.apply({"params": p}, ids[:, :1], attention_mask=slots, cache=c,
                                     cache_index=jnp.asarray(12, jnp.int32), kv_extents=(8, 16)))(params, cache)),
    }
    clean = lambda text: re.sub(r"0x[0-9a-f]+", "0x", text)
    return {k: hashlib.sha256(clean(v).encode()).hexdigest() for k, v in texts.items()}


@pytest.mark.parametrize("family", RECORDED_FAMILIES)
def test_presets_trace_to_the_programs_recorded_before_the_family(family, clean_trace_state):
    """Recorded on PR 60's parent by this function, before ``LayerLayout``
    gained the ``conv`` mixer, ``make_kv_cache`` its new kind of layer,
    ``MoEMLP`` the renormalisation's epsilon and ``TransformerConfig`` their
    fields: parameter tree, cache tree, train step and decode step of every
    accepted configuration's toy byte for byte."""
    with open(RECORDED) as f:
        assert program_fingerprints(family) == json.load(f)[family]


# ---------------------------------------------------------------------------
# trlx_tpu.train(): the normal GRPO path
# ---------------------------------------------------------------------------


def test_collection_counters_tell_the_conv_rows_from_k_and_v():
    from trlx_tpu.data.default_configs import default_grpo_config
    from trlx_tpu.trainer.grpo import GRPOTrainer

    cfg = default_grpo_config().evolve(
        tokenizer=dict(tokenizer_path="builtin:bytes"), train=dict(tracker=None),
        model=dict(model_path="builtin:lfm2-test", num_layers_unfrozen=2),
        parallel=dict(param_dtype="float32", compute_dtype="float32"))
    trainer = GRPOTrainer(cfg, reward_fn=lambda samples, **kw: [0.0] * len(samples))
    trainer._note_dense_kv_gauge((3, 21), GenerationConfig(max_new_tokens=19))
    assert trainer.last_cache_stats == {
        "rollout/kv_cache_bytes": float(2 * 2 * 3 * 40 * 2 * 16 * 4), "rollout/ssm_state_bytes": 0.0, "rollout/kv_lane_heads": 1.0,
        "rollout/conv_cache_bytes": float(4 * 3 * 2 * 64 * 4)}, trainer.last_cache_stats
    assert trainer.last_kv_layers == ((40, False), (40, False))  # a conv layer has no slots to read


def test_train_runs_grpo_on_the_preset_and_logs_its_counters(tmp_path):
    """``trlx_tpu.train()`` on ``builtin:lfm2-test`` holding experts 2 and 3:
    the same trainer, collector, sampler, scoring forward, hydra branch and
    train step as every other preset. Policy and branch start at KL 0; after
    two steps every kind of leaf has changed but the selection bias; the
    records carry the conv rows' bytes beside K and V's and the held share."""
    import trlx_tpu.trlx as trlx
    from trlx_tpu.data.default_configs import default_grpo_config

    config = default_grpo_config().evolve(
        train=dict(seq_length=32, batch_size=4, total_steps=2, eval_interval=10,
                   checkpoint_interval=10, epochs=1, save_best=False, tracker=None,
                   checkpoint_dir=str(tmp_path / "ckpts"), logging_dir=str(tmp_path / "logs")),
        model=dict(model_path="builtin:lfm2-test", num_layers_unfrozen=2,
                   model_extra_kwargs=dict(moe_experts_held=2, moe_first_expert=2)),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        method=dict(num_rollouts=8, chunk_size=8, group_size=4, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=12, min_new_tokens=12, top_k=0, top_p=1.0, do_sample=True)),
    )
    records, before = [], {}

    def hook(trainer):
        trainer.tracker = types.SimpleNamespace(
            log=lambda stats, step=None: records.append(dict(stats)), finish=lambda: None)
        before.update({jax.tree_util.keystr(p): np.asarray(x) for p, x in jax.tree_util.tree_leaves_with_path(trainer.state.params)})

    rng = np.random.RandomState(0)
    prompts = ["".join(chr(97 + c) for c in rng.randint(0, 26, size=20)) for _ in range(2)]
    trainer = trlx.train(
        reward_fn=lambda samples, prompts, outputs, **kw: [float(i) for i, _ in enumerate(outputs)],
        prompts=prompts, config=config, init_trainer_hook=hook)
    assert trainer.tcfg.model_type == "lfm2_moe" and trainer.tcfg.experts_held == 2
    collection = next(r for r in records if "time/exp" in r)
    width = trainer.tcfg.dtype.dtype.itemsize
    assert collection["rollout/conv_cache_bytes"] == 4 * 8 * 2 * 64 * width
    S = int(collection["rollout/kv_cache_bytes"] // (2 * 2 * 8 * 2 * 16 * width))
    assert 32 <= S <= 40 and collection["rollout/kv_cache_bytes"] == 2 * 2 * 8 * S * 2 * 16 * width
    assert abs(float(collection.get("policy/sqrt_kl", 0.0))) < 1e-3
    step = next(r for r in records if "time/train_step" in r)
    assert 0.0 < float(step["moe/held_frac"]) < 0.7 and float(step["moe/dropped_frac"]) == 0.0
    assert np.isfinite([v for k, v in step.items() if k.startswith("losses/")]).all()
    after = {jax.tree_util.keystr(p): np.asarray(x) for p, x in jax.tree_util.tree_leaves_with_path(trainer.state.params)}
    moved = {name for name in after if not np.array_equal(after[name], before[name])}
    for needle in ("['h_0']['attn']['in_proj']", "['h_0']['attn']['conv_weight']", "['h_0']['attn']['out_proj']",
                   "['h_2']['attn']['q_norm']", "['h_3']['mlp']['w_up']", "['h_3']['mlp']['router']['kernel']", "['wte']"):
        assert any(needle in name for name in moved), needle
    assert not any("router_bias" in name for name in moved)
