"""NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type`` ``nemotron_h``): layers of
ONE sublayer each, said by the published ``hybrid_override_pattern``: ``M`` a
Mamba-2 mixer alone (its float32 state and its conv's last rows the layer's
whole cache), ``*`` GQA attention alone without rotary, ``E`` sigmoid-routed
experts of two matrices and ``relu(x)^2`` beside a shared expert of its own
width (no cache at all); against the plain float32 reference the benchmark
keeps (``chipbench/reference/nemotron_h.py``: the recurrence token by token)
at toy widths on the CPU.

``builtin:nemotron-h-test``: the benchmark's nine letters ``MEMEM*EME``;
hidden 64, Mamba heads 4 x 16 in 2 groups, state 32, chunk 8, conv 4; GQA 4/2
of 16; 8 experts of 32 top-2 under a bias, a shared expert of 64.
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
from chipbench.reference import nemotron_h as ref
from trlx_tpu.models.transformer import CausalTransformer, config_from_spec, get_activation, make_kv_cache
from trlx_tpu.ops.cache_layout import KINDS, KV, PATHS, RECURRENT, cache_bytes, cache_slots, cacheless, describe, refuse

TOL = 2e-5  # relative L2 of float32 logits: what is left is the order of summation (the chunked scan against token by token)
FAULT_FLOOR = 5e-3

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
CFG = config_from_spec("builtin:nemotron-h-test", attention_impl="xla", **F32)
HELD = dataclasses.replace(CFG, moe_experts_held=2, moe_first_expert=2)  # one chip's share: experts 2 and 3 of the router's 8
MODEL = CausalTransformer(CFG)
PATTERN = "MEMEM*EME"
M_LAYERS, E_LAYERS, ATTN_LAYER = (0, 2, 4, 7), (1, 3, 6, 8), 5
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CONFIG, CELL = "nemotron3-nano-30b-a3b-l9e8", "nemotron3nano_grpo_reason_r128"


def dims_of(cfg):
    """The published keys the reference reads, from the config under test."""
    return dict(
        num_hidden_layers=cfg.num_layers, hybrid_override_pattern=cfg.hybrid_override_pattern, norm_eps=cfg.layer_norm_epsilon,
        num_attention_heads=cfg.num_heads, num_key_value_heads=cfg.kv_heads, head_dim=cfg.dims_per_head, rope_theta=cfg.rope_theta,
        mamba_num_heads=cfg.mamba_heads, mamba_head_dim=cfg.mamba_head_dim, n_groups=cfg.mamba_groups, ssm_state_size=cfg.mamba_state,
        chunk_size=cfg.mamba_chunk, num_experts_per_tok=cfg.num_experts_per_tok, routed_scaling_factor=cfg.routed_scaling_factor,
        moe_intermediate_size=cfg.moe_intermediate_size, n_routed_experts=cfg.experts_held, moe_first_expert_held=cfg.moe_first_expert,
    )


DIMS = dims_of(CFG)


def seeded(params, seed=0):
    """Weights at which every mechanism shows: matrices of unit gain, norm
    scales scattered about 1, a conv bias and a selection bias that bind."""

    def leaf(path, x):
        name = jax.tree_util.keystr(path)
        rs = np.random.RandomState(int(hashlib.sha256(f"{seed}{name}".encode()).hexdigest()[:8], 16))
        if name.endswith("['scale']") or name.endswith("['norm_scale']"):
            return jnp.asarray(1.0 + 0.3 * rs.randn(*x.shape), x.dtype)
        if name.endswith("['router_bias']"):
            return jnp.asarray(0.2 * rs.randn(*x.shape), x.dtype)
        if name.endswith("['conv_bias']"):
            return jnp.asarray(0.5 * rs.randn(*x.shape), x.dtype)
        if name.endswith("['kernel']") or x.ndim == 3:
            return jnp.asarray(rs.randn(*x.shape) / np.sqrt(x.shape[-2]), x.dtype)
        return x  # the embedding (std 1), the taps, A_log, dt_bias and D as drawn

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


def test_every_layer_is_one_norm_one_sublayer_under_the_names_of_that_half():
    assert [(l.mixer, l.ffn) for l in CFG.layer_layouts] == [
        {"M": ("mamba2", "none"), "*": ("attention", "none"), "E": ("none", "moe")}[c] for c in PATTERN]
    assert not any(l.rotary or l.window for l in CFG.layer_layouts)
    for i, letter in enumerate(PATTERN):
        want = {"M": {"ln_attn", "mixer"}, "*": {"ln_attn", "attn"}, "E": {"ln_mlp", "mlp"}}[letter]
        assert set(PARAMS[f"h_{i}"]) == want, i
    assert set(PARAMS["h_1"]["mlp"]) == {"router", "router_bias", "shared_expert", "w_up", "w_down"}  # two matrices an expert
    assert PARAMS["h_1"]["mlp"]["shared_expert"]["up_proj"]["kernel"].shape == (64, 64)  # its own width, twice the routed 32
    assert "lm_head" in PARAMS and "wpe" not in PARAMS


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("cfg", [CFG, HELD], ids=["all_experts", "experts_2_and_3"])
def test_logits_match_the_reference_on_left_padded_rows_of_unlike_length(cfg, impl):
    T = 40
    ids, mask = batch(T, [0, 5, 11])
    params = init(cfg, seed=3)
    model = CausalTransformer(dataclasses.replace(cfg, attention_impl=impl))
    got = model.apply({"params": params}, ids, attention_mask=mask)["logits"]
    want = ref.logits(params, dims_of(cfg), ids, mask, (0, T))
    assert got.shape == (3, T, 259)
    assert rel(got, want, mask) < TOL


def test_a_rows_logits_do_not_depend_on_the_padding_in_front_of_it():
    """A padded slot feeds nothing into the state or the conv window: zeros
    left of the row's first real token, as left of a row that starts at slot 0."""
    T, pad = 30, 13
    ids, mask = batch(T, [pad])
    padded = MODEL.apply({"params": PARAMS}, ids, attention_mask=mask)["logits"][0, pad:]
    alone = MODEL.apply({"params": PARAMS}, ids[:, pad:], attention_mask=mask[:, pad:])["logits"][0]
    assert float(jnp.abs(padded - alone).max()) < TOL * float(jnp.abs(alone).max())


@pytest.mark.parametrize("P", [1, 3, 12], ids=["prefill_of_one", "prefill_shorter_than_the_conv", "prefill_12"])
def test_prefill_then_16_steps_through_the_cache_match_the_references_full_forward(P):
    """The sampler's two programs: a span from slot 0 (the chunked scan from a
    zero state), then sixteen single tokens (``ssd_step`` on the carried state
    and conv rows on ``M``, K and V under static extents on ``*``, nothing on
    ``E``), against the reference's full forward."""
    T = P + 16
    ids, mask = batch(T, [0, 0, 0] if P < 4 else [0, 4, 9])
    want = ref.logits(PARAMS, DIMS, ids, mask, (0, T))
    cache = make_kv_cache(CFG, 3, T, jnp.float32)
    assert [set(layer) for layer in cache] == [{"M": {"ssm", "conv"}, "*": {"k", "v"}, "E": set()}[c] for c in PATTERN]
    out = MODEL.apply({"params": PARAMS}, ids[:, :P], attention_mask=mask, cache=cache, cache_index=0)
    logits, cache = [out["logits"]], out["cache"]
    for t in range(P, T):
        out = MODEL.apply({"params": PARAMS}, ids[:, t : t + 1], attention_mask=mask, cache=cache, cache_index=t, kv_extents=(16, T))
        logits.append(out["logits"])
        cache = out["cache"]
    assert [set(layer) for layer in cache] == [{"M": {"ssm", "conv"}, "*": {"k", "v"}, "E": set()}[c] for c in PATTERN]
    assert cache[0]["ssm"].dtype == jnp.float32 and cache[0]["ssm"].shape == (3, 4, 16, 32) and cache[0]["conv"].shape == (3, 3, 192)
    assert rel(jnp.concatenate(logits, axis=1), want, mask) < TOL


@pytest.mark.parametrize("fault", ref.FAULTS + ref.PRECISION_CONTROLS)
def test_every_planted_fault_of_the_reference_is_caught(fault):
    T = 40
    ids, mask = batch(T, [0, 5, 11])
    got = MODEL.apply({"params": PARAMS}, ids, attention_mask=mask)["logits"]
    assert rel(got, ref.logits(PARAMS, DIMS, ids, mask, (0, T)), mask) < TOL
    assert rel(got, ref.logits(PARAMS, DIMS, ids, mask, (0, T), fault=fault), mask) > FAULT_FLOOR


def test_the_hydra_branch_replays_an_m_layer_and_an_e_layer():
    """``forward_branch`` over the last two layers (an ``M`` from a zero
    state, then an ``E``) on the trunk's activations gives the full pass's
    logits, on left-padded rows."""
    T = 24
    ids, mask = batch(T, [0, 6])
    out = MODEL.apply({"params": PARAMS}, ids, attention_mask=mask, branch_layer=2)
    branch = MODEL.apply({"params": PARAMS}, out["branch_input"], 2, mask, method=MODEL.forward_branch)
    assert rel(branch["logits"], out["logits"], mask) < 1e-6


@pytest.mark.parametrize("cfg", [CFG, HELD], ids=["all_experts", "experts_2_and_3"])
def test_gradients_of_a_summed_logprob_loss_match_the_references_for_every_leaf(cfg):
    """The response's summed logprob with respect to EVERY leaf (a GRPO job
    trains them all): through the chunked scan, the conv, the gated norm,
    ``A_log``, ``dt_bias`` and ``D`` of the ``M`` layers, the attention layer,
    the router, the held and the shared experts' ``relu^2``, the embedding and
    the untied head, against autodiff through the token-by-token reference."""
    from trlx_tpu.utils.stats import logprobs_of_labels

    T, Q = 30, 14
    params, (ids, mask) = init(cfg, seed=7), batch(T, [0, 3, 9], seed=7)

    def objective(logits_of):
        return lambda p: jnp.sum(logprobs_of_labels(logits_of(p), ids[:, Q:]) * mask[:, Q:])

    model = CausalTransformer(cfg)
    loss, got = jax.value_and_grad(objective(lambda p: model.apply(
        {"params": p}, ids, attention_mask=mask, logits_span=(Q - 1, T - 1))["logits"]))(params)
    want_loss, want = jax.value_and_grad(objective(lambda p: ref.logits(p, dims_of(cfg), ids, mask, (Q - 1, T - 1))))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * abs(float(want_loss))
    seen = set()
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']"):  # selects, weighs nothing, takes no gradient
            assert float(jnp.abs(g).max()) == 0.0 and float(jnp.abs(w).max()) == 0.0
            continue
        err = float(jnp.linalg.norm(g - w) / jnp.maximum(jnp.linalg.norm(w), 1e-12))
        assert err < 2e-4, (name, err)
        if float(jnp.linalg.norm(w)) > 0:
            seen.add(re.sub(r"h_\d+", "h", name))
    for needle in ("['mixer']['in_proj']", "['mixer']['conv_weight']", "['mixer']['conv_bias']", "['mixer']['A_log']", "['mixer']['dt_bias']",
                   "['mixer']['D']", "['mixer']['norm_scale']", "['mixer']['out_proj']", "['attn']['q_proj']", "['attn']['o_proj']",
                   "['mlp']['router']", "['mlp']['w_up']", "['mlp']['w_down']", "['shared_expert']['up_proj']", "['ln_attn']", "['ln_mlp']",
                   "['wte']", "['lm_head']"):
        assert any(needle in name for name in seen), needle


# ---------------------------------------------------------------------------
# the shares, the preset, the configuration file, the cache's description
# ---------------------------------------------------------------------------


def test_the_four_shares_of_an_e_layer_add_up_to_the_uncut_layer():
    """The guide's section 4: a layer that holds experts ``[first, first +
    held)`` of the router's width returns the part its own give, and the
    shared expert whole, as every chip computes it: the four shares' held
    parts and the shared expert counted ONCE are the uncut layer, program and
    reference alike."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 64))
    mlp = init(seed=4)["h_1"]["mlp"]
    ones = np.ones((2, 9))
    full, _ = tf.MoEMLP(CFG).apply({"params": mlp}, x)
    assert rel(full, ref.moe_layer(mlp, x, 2, 2.5), ones) < TOL
    shared = tf.MLP(CFG, 64).apply({"params": mlp["shared_expert"]}, x)
    assert rel(shared, full - ref.moe_layer(mlp, x, 2, 2.5, shared=False), ones) < TOL
    total = shared
    for first in range(0, 8, 2):
        cut = dataclasses.replace(CFG, moe_experts_held=2, moe_first_expert=first)
        held = dict(mlp, **{k: mlp[k][first : first + 2] for k in ("w_up", "w_down")})
        share, _ = tf.MoEMLP(cut).apply({"params": held}, x)
        assert rel(share, ref.moe_layer(held, x, 2, 2.5, first=first), ones) < TOL
        total = total + (share - shared)  # what every chip computes alike is counted once
    assert rel(total, full, ones) < TOL


def catalog_row():
    with open(CATALOG) as f:
        return next(row for row in map(json.loads, f) if row["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")


def count(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def shapes_of(cfg):
    return jax.eval_shape(lambda: CausalTransformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])


def test_the_preset_holds_the_published_keys_and_counts_the_cards_parameters():
    big, published = config_from_spec("builtin:nemotron3-nano-30b-a3b"), catalog_row()["config"]
    letters = {("mamba2", "none"): "M", ("attention", "none"): "*", ("none", "moe"): "E"}
    assert "".join(letters[l.mixer, l.ffn] for l in big.layer_layouts) == published["hybrid_override_pattern"] == big.hybrid_override_pattern
    assert [sum(c == k for c in published["hybrid_override_pattern"]) for k in "M*E"] == [23, 6, 23]
    fields = dict(
        hidden_size="hidden_size", intermediate_size="intermediate_size", moe_intermediate_size="moe_intermediate_size",
        moe_shared_expert_intermediate_size="moe_shared_expert_intermediate_size", num_attention_heads="num_heads",
        num_key_value_heads="num_kv_heads", head_dim="head_dim", num_hidden_layers="num_layers", n_routed_experts="num_experts",
        n_shared_experts="num_shared_experts", num_experts_per_tok="num_experts_per_tok", norm_topk_prob="moe_renormalize",
        routed_scaling_factor="routed_scaling_factor", mamba_num_heads="mamba_heads", mamba_head_dim="mamba_head_dim",
        n_groups="mamba_groups", ssm_state_size="mamba_state", conv_kernel="mamba_conv", chunk_size="mamba_chunk",
        norm_eps="layer_norm_epsilon", mlp_hidden_act="activation", attention_bias="attn_bias", mlp_bias="mlp_bias",
        tie_word_embeddings="tie_word_embeddings", rope_theta="rope_theta", vocab_size="vocab_size",
        max_position_embeddings="max_position_embeddings", model_type="model_type")
    for key, field in fields.items():
        assert getattr(big, field) == published[key], key
    assert (big.position_scheme, big.moe_gated, big.moe_scoring, big.moe_topk_method, big.moe_capacity_factor) == ("none", False, "sigmoid", "noaux_tc", 0)
    assert (big.mamba_d_ssm, big.mamba_conv_channels) == (4096, 6144) and abs(big.mamba_in_proj_init_std * np.sqrt(2688) - 1.0) < 1e-6 and big.embed_init_std == 32.0
    assert hash(big) == hash(config_from_spec("builtin:nemotron3-nano-30b-a3b"))
    assert count(shapes_of(big)) == 31_577_940_288  # the card's 31.6 B


@pytest.mark.parametrize("override,match", [
    (dict(hybrid_override_pattern=None, mixer_layout=("none",) * 9, ffn_layout=("none",) * 9), "neither a sequence mixer .* nor a feed-forward part"),
    (dict(hybrid_override_pattern="MEMEM-EME"), "letters M .*, \\* .* and E"),
    (dict(hybrid_override_pattern="MEME"), "for each of 9 layers"),
    (dict(hybrid_override_pattern=None, mixer_layout=("mamba2", "none") * 5, ffn_layout=None), "needs ffn_layout"),
    (dict(mamba_heads=0), "mamba2 layers among attention layers"),
    (dict(parallel_residual=True), "ONE sublayer .* sequential residual path"),
    (dict(mixer="mamba2"), "no second mixer"),
])
def test_the_config_refuses_a_layout_it_cannot_run(override, match):
    with pytest.raises(ValueError, match=match):
        config_from_spec("builtin:nemotron-h-test", **override)


def test_a_layer_of_both_sublayers_still_runs_under_the_two_lists():
    """``mixer_layout`` and ``ffn_layout`` are the general form the pattern is
    one way of saying: a Mamba-2 mixer in front of experts in ONE layer is the
    two-sublayer block, with both norms."""
    cfg = config_from_spec("builtin:nemotron-h-test", num_layers=2, hybrid_override_pattern=None,
                           mixer_layout=("mamba2", "attention"), ffn_layout=("moe", "none"), attention_impl="xla", **F32)
    params = shapes_of(cfg)
    assert set(params["h_0"]) == {"ln_attn", "mixer", "ln_mlp", "mlp"} and set(params["h_1"]) == {"ln_attn", "attn"}
    assert [set(layer) for layer in jax.eval_shape(lambda: make_kv_cache(cfg, 1, 8))] == [{"ssm", "conv"}, {"k", "v"}]


def test_scan_layers_refuses_the_mixed_stack_by_name():
    with pytest.raises(NotImplementedError, match="scan_layers.*nemotron_h"):
        CausalTransformer(config_from_spec("builtin:nemotron-h-test", scan_layers=True)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_the_cut_is_the_configuration_files_and_its_arithmetic_holds():
    from chipbench import job
    from trlx_tpu.data.configs import ModelConfig, ParallelConfig

    file, row = job.load_config(CONFIG), catalog_row()
    assert file["source"] == row["source_url"] and file["family"] == "nemotron_h" and file["router_width"] == 128
    for key, value in row["config"].items():  # every number of the catalog's config under the same key, but the reduced ones
        if key not in file["reduced"]:
            assert file["published"][key] == value, key
    reduced = ["hybrid_override_pattern", "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert sorted(file["reduced"]) == reduced == sorted(next(c["reduced"] for c in job.load_benchmark()["configs"] if c["name"] == CONFIG))
    published = file["published"]
    assert published["hybrid_override_pattern"] == row["config"]["hybrid_override_pattern"][:9] == PATTERN
    assert (published["num_hidden_layers"], published["n_routed_experts"], published["vocab_size"]) == (9, 8, 16384)
    model = file["job"]["model"]
    cut = config_from_spec(model["model_path"], **model["model_extra_kwargs"])
    assert (cut.num_experts, cut.experts_held, cut.moe_first_expert, cut.num_experts_per_tok) == (128, 8, 0, 6)
    cfg = types.SimpleNamespace(model=ModelConfig(**model), parallel=ParallelConfig(**file["job"]["parallel"]))
    job.check_published_widths(cfg, file)
    shapes = shapes_of(cut)
    assert count(shapes) == 666_963_456 == file["table"]["layers 0 to 8 with the slice"]
    assert (count(shapes["h_0"]), count(shapes["h_5"]), count(shapes["h_1"])) == (38_744_896, 23_399_040, 20_302_592 + 8 * 9_977_856)
    assert shapes["h_1"]["mlp"]["w_up"].shape == (8, 2688, 1856) and shapes["h_1"]["mlp"]["router"]["kernel"].shape == (2688, 128)
    assert shapes["h_1"]["mlp"]["shared_expert"]["up_proj"]["kernel"].shape == (2688, 3712)
    assert count(shapes["h_7"]) + count(shapes["h_8"]) + count(shapes["ln_f"]) + count(shapes["lm_head"]) == 182_913_216
    assert sum(n * times for n, times in (v for v in file["table"].values() if isinstance(v, list))) == 666_963_456
    cache = jax.eval_shape(lambda: make_kv_cache(cut, 128, 1152))
    for i, letter in enumerate(PATTERN):
        got = {k: (v.shape, str(v.dtype)) for k, v in cache[i].items()}
        assert got == {"M": {"ssm": ((128, 64, 64, 128), "float32"), "conv": ((128, 3, 6144), "bfloat16")},
                       "*": {"k": ((128, 1152, 2, 128), "bfloat16"), "v": ((128, 1152, 2, 128), "bfloat16")}, "E": {}}[letter], i
    held = cache_bytes(cache, 1152)
    assert (held[RECURRENT], held[KV], cacheless(cache)) == (1_092_616_192, 150_994_944, 4) and set(held) == {RECURRENT, KV}
    traffic = job.load_json("traffic", "grpo_reason_r128")
    assert traffic["job"]["method"] == dict(num_rollouts=128, chunk_size=128, group_size=8, ppo_epochs=1)
    toy = job.load_config(CONFIG, toy=True)
    toy_model = dict(model, **toy["toy"]["model"])
    toy_cfg = config_from_spec(toy_model["model_path"], **toy_model["model_extra_kwargs"])
    for key, value in dims_of(toy_cfg).items():
        if key not in ("moe_first_expert_held", "norm_eps", "rope_theta", "routed_scaling_factor"):
            assert toy["published"][key] == value, key


def test_a_mamba_layers_cache_is_recurrent_and_an_e_layers_is_empty():
    """``{"ssm", "conv"}`` with no K or V beside them is still kind
    ``recurrent`` (the conv rows with their neighbour), has no slot axis; an
    ``E`` layer's empty dict has no leaf to describe and is counted apart."""
    cache = jax.eval_shape(lambda: make_kv_cache(CFG, 3, 20))
    assert sorted((h.name, h.kind, h.slots) for h in describe(cache[0])) == [("conv", RECURRENT, None), ("ssm", RECURRENT, None)]
    assert describe(cache[1]) == [] and cache_slots(cache[1]) is None and cache_slots(cache[0]) is None and cache_slots(cache[ATTN_LAYER]) == 20
    held = cache_bytes(cache, 20)
    assert held[RECURRENT] == 4 * 3 * (4 * 16 * 32 * 4 + 3 * 192 * 4) and held[KV] == 2 * 3 * 20 * 2 * 16 * 4 and set(held) == {RECURRENT, KV}
    assert cacheless(cache) == 4 and cacheless(make_kv_cache(config_from_spec("builtin:falconh1-test"), 1, 8)) == 0
    assert "beside K and V" in KINDS[RECURRENT] and "whole cache" in KINDS[RECURRENT]


@pytest.mark.parametrize("path", PATHS)
def test_whole_row_paths_refuse_the_stack_by_name(path):
    """Slot refill, the paged Engine, the prefix cache and speculation refuse
    a Mamba-2 state by the sentences they have, with or without K and V
    beside it; the attention layer and the empty layers alone are held."""
    cache = jax.eval_shape(lambda: make_kv_cache(CFG, 1, 8))
    with pytest.raises(NotImplementedError, match=rf"^{path} .*recurrent state \(beside K and V, or with its conv's rows a layer's whole cache\) \(leaves \['conv', 'ssm'\]\): .*B7[bc]\)"):
        refuse(cache, path, 8)
    refuse([layer for i, layer in enumerate(cache) if i not in M_LAYERS], path, 8)


@pytest.mark.parametrize("way", ["import", "export"])
def test_hf_interop_says_there_is_no_converter(way):
    from trlx_tpu.models.hf_interop import UnsupportedHFExport, config_from_hf, hf_config_from_transformer

    if way == "import":
        with pytest.raises(ValueError, match="nemotron_h.*no HF checkpoint conversion.*B7"):
            config_from_hf(types.SimpleNamespace(model_type="nemotron_h"))
    else:
        with pytest.raises(UnsupportedHFExport, match="nemotron_h.*no HF checkpoint conversion"):
            hf_config_from_transformer(CFG)


@pytest.mark.parametrize("x,value,slope", [(-1.5, 0.0, 0.0), (0.0, 0.0, 0.0), (1.5, 2.25, 3.0)], ids=["below", "at_zero", "above"])
def test_relu2_and_its_gradient(x, value, slope):
    act = get_activation("relu2")
    got, grad = jax.value_and_grad(act)(jnp.float32(x))
    assert (float(got), float(grad)) == (value, slope)


def test_the_non_gated_experts_run_under_a_scope_of_their_own():
    """``trlx/relu2_experts`` around the activation between the two grouped
    matmuls, so that a trace tells it from the gated form's; no third matrix."""
    x = jnp.ones((1, 4, 64), jnp.float32)
    mlp = PARAMS["h_1"]["mlp"]
    text = jax.jit(lambda p, a: tf.MoEMLP(CFG).apply({"params": p}, a)[0]).lower(mlp, x).as_text(debug_info=True)
    assert "trlx/relu2_experts" in text and "w_gate" not in mlp


# ---------------------------------------------------------------------------
# the benchmark's entries
# ---------------------------------------------------------------------------

NEW_METRICS = ("mamba_step_device_ms", "mamba_step_roofline", "mamba_scan_device_ms", "mamba_scan_roofline", "cacheless_layers")
APPENDED_TO = ("ssm_state_gib", "kv_cache_gib", "moe_held_pct", "moe_held_imbalance", "moe_share_gmm_device_ms", "moe_gmm_roofline",
               "moe_compact_pct")


@pytest.mark.parametrize("name", NEW_METRICS + APPENDED_TO)
def test_the_cells_metrics_are_declared_and_their_files_name_what_the_harness_finds(name):
    """Each new metric lists the new cell alone and agrees with its file; each
    accepted metric the cell joins lists it last, and its file is the accepted
    one (a reducer the harness has, a key the program logs or a pattern that
    compiles, a cost function the family's file or ``flops.py`` brings)."""
    from chipbench import flops, job, layers

    entry = next(m for m in job.load_benchmark()["per_layer"] if m["name"] == name)
    spec = layers.metric_files()[name]
    assert all(entry[k] == spec[k] for k in ("unit", "better", "source", "layer", "moves"))
    assert entry["workloads"][-1] == CELL and (entry["workloads"] == [CELL]) == (name in NEW_METRICS)
    if "pattern" in spec:
        re.compile(spec["pattern"])
    assert "TBD" not in spec.get("pattern", "") + spec["reads"]
    if "costs" in spec:
        model = types.SimpleNamespace(family=flops.family_module("nemotron_h"))
        assert callable(flops.kernel_costs(spec["costs"], model))
    if "key" in spec:
        trainer_dir = os.path.join(os.path.dirname(tf.__file__), "..", "trainer")
        source = open(os.path.join(trainer_dir, "base.py")).read() + open(os.path.join(trainer_dir, "ppo.py")).read()
        assert f'"{spec["key"]}"' in source


def test_the_step_pattern_finds_the_state_fusion_and_not_the_loop_around_it():
    from chipbench import layers

    pattern = re.compile(layers.metric_files()["mamba_step_device_ms"]["pattern"])
    assert layers.metric_files()["mamba_step_roofline"]["pattern"] == pattern.pattern
    step = "%multiply_reduce_fusion.7 = (bf16[128,64,64]{2,1,0}, f32[128,64,64,128]{3,2,1,0}) fusion(f32[128,64,64,128]{3,2,1,0} %p, bf16[128,64]{1,0} %q), kind=kLoop"
    loop = "%while.3 = (s32[], f32[128,64,64,128]{3,2,1,0}) while((s32[], f32[128,64,64,128]{3,2,1,0}) %t), condition=%c, body=%b"
    learner = "%fusion.9 = f32[8,64,64,128]{3,2,1,0} fusion(f32[8,64,64,128]{3,2,1,0} %p), kind=kLoop"
    operand_only = "%fusion.11 = bf16[128,64,64]{2,1,0} fusion(f32[128,64,64,128]{3,2,1,0} %p), kind=kLoop"
    assert pattern.search(step) and not pattern.search(loop) and not pattern.search(learner) and not pattern.search(operand_only)


# ---------------------------------------------------------------------------
# trlx_tpu.train(): the normal GRPO path
# ---------------------------------------------------------------------------


def test_collection_counters_tell_the_state_from_k_and_v_and_count_the_empty_layers():
    from trlx_tpu.data.default_configs import default_grpo_config
    from trlx_tpu.ops.sampling import GenerationConfig
    from trlx_tpu.trainer.grpo import GRPOTrainer

    cfg = default_grpo_config().evolve(
        tokenizer=dict(tokenizer_path="builtin:bytes"), train=dict(tracker=None),
        model=dict(model_path="builtin:nemotron-h-test", num_layers_unfrozen=2),
        parallel=dict(param_dtype="float32", compute_dtype="float32"))
    trainer = GRPOTrainer(cfg, reward_fn=lambda samples, **kw: [0.0] * len(samples))
    trainer._note_dense_kv_gauge((3, 21), GenerationConfig(max_new_tokens=19))
    assert trainer.last_cache_stats == {
        "rollout/kv_cache_bytes": float(2 * 3 * 40 * 2 * 16 * 4), "rollout/ssm_state_bytes": float(4 * 3 * (4 * 16 * 32 * 4 + 3 * 192 * 4)),
        "rollout/kv_lane_heads": 1.0, "rollout/cacheless_layers": 4.0}, trainer.last_cache_stats
    assert trainer.last_kv_layers == ((40, False),)  # the one layer with slots to read


def test_train_runs_grpo_on_the_preset_and_logs_its_counters(tmp_path):
    """``trlx_tpu.train()`` on ``builtin:nemotron-h-test`` holding experts 2
    and 3: the same trainer, collector, sampler, scoring forward, hydra branch
    and train step as every other preset. Policy and branch start at KL 0;
    after two steps a leaf of each kind of layer has changed; the records
    carry the state's bytes, K and V's, the empty layers and the held share."""
    import trlx_tpu.trlx as trlx
    from trlx_tpu.data.default_configs import default_grpo_config

    config = default_grpo_config().evolve(
        train=dict(seq_length=32, batch_size=4, total_steps=2, eval_interval=10,
                   checkpoint_interval=10, epochs=1, save_best=False, tracker=None,
                   checkpoint_dir=str(tmp_path / "ckpts"), logging_dir=str(tmp_path / "logs")),
        model=dict(model_path="builtin:nemotron-h-test", num_layers_unfrozen=2,
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
    assert trainer.tcfg.model_type == "nemotron_h" and trainer.tcfg.experts_held == 2
    collection = next(r for r in records if "time/exp" in r)
    width = trainer.tcfg.dtype.dtype.itemsize
    assert collection["rollout/ssm_state_bytes"] == 4 * 8 * (4 * 16 * 32 * 4 + 3 * 192 * width)
    S = int(collection["rollout/kv_cache_bytes"] // (2 * 8 * 2 * 16 * width))
    assert 32 <= S <= 40 and collection["rollout/kv_cache_bytes"] == 2 * 8 * S * 2 * 16 * width
    assert collection["rollout/cacheless_layers"] == 4.0
    assert abs(float(collection.get("policy/sqrt_kl", 0.0))) < 1e-3
    step = next(r for r in records if "time/train_step" in r)
    assert 0.0 < float(step["moe/held_frac"]) < 0.7 and float(step["moe/dropped_frac"]) == 0.0
    assert np.isfinite([v for k, v in step.items() if k.startswith("losses/")]).all()
    after = {jax.tree_util.keystr(p): np.asarray(x) for p, x in jax.tree_util.tree_leaves_with_path(trainer.state.params)}
    moved = {name for name in after if not np.array_equal(after[name], before[name])}
    for needle in ("['h_0']['mixer']['in_proj']", "['h_0']['mixer']['A_log']", "['h_5']['attn']['q_proj']", "['h_1']['mlp']['w_up']",
                   "['h_1']['mlp']['shared_expert']", "['h_1']['mlp']['router']['kernel']", "['wte']", "['lm_head']"):
        assert any(needle in name for name in moved), needle
    assert not any("router_bias" in name for name in moved)
