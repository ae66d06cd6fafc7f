"""openPangu-Ultra-MoE (latent attention with a latent cache, a leading dense
layer before sparse ones, a shared expert beside sigmoid-routed ones, sandwich
norms, a chip's share of the experts) against the plain float32 reference the
benchmark keeps, ``chipbench/reference/pangu_ultra_moe.py``.

Toy size on the CPU (``builtin:pangu-test``: 1 dense + 2 sparse layers, hidden
64, 4 heads whose q/k are 24 = 16 + 8 and whose v is 16, latents of 32 and 16,
8 experts of 32 top-2 and a shared one), float32 on both sides, so the
mathematics has to agree: the full forward; the sampler's prefill and its
single-token steps through the latent cache, which run the ABSORBED form,
against the reference's expanded full forward; every planted fault; the four
shares adding up with the shared expert counted once; PPO with LoRA and GRPO
through ``trlx_tpu.train()``; and each KV-only path refusing by name.
"""

import dataclasses
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import pangu_ultra_moe as reference
from trlx_tpu.models.transformer import (
    CausalTransformer,
    LayerLayout,
    MoEMLP,
    TransformerConfig,
    config_from_spec,
    make_kv_cache,
)
from trlx_tpu.ops import sampling
from trlx_tpu.ops.cache_layout import LATENT, cache_bytes, refuse
from trlx_tpu.ops.sampling import GenerationConfig, generate

# Relative L2 of the logits. Both sides compute in float32 on the CPU; what is
# left is the order of summation (grouped matmuls against one dense pass an
# expert; a query folded through kv_b_proj against keys built from it).
TOL = 1e-4

CFG = TransformerConfig.pangu("test", param_dtype=jnp.float32, dtype=jnp.float32, attention_impl="xla")
# one chip's share: experts 2 and 3 of the router's 8
HELD = dataclasses.replace(CFG, moe_experts_held=2, moe_first_expert=2)
B, T = 3, 40


def dims_of(cfg):
    return {
        "num_hidden_layers": cfg.num_layers,
        "first_k_dense_replace": cfg.first_k_dense,
        "num_attention_heads": cfg.num_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim,
        "rms_norm_eps": cfg.layer_norm_epsilon,
        "rope_theta": cfg.rope_theta,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "n_routed_experts": cfg.experts_held,
        "moe_first_expert_held": cfg.moe_first_expert,
        "lora_alpha": cfg.lora_alpha,
    }


def seeded_params(seed, cfg=CFG):
    """The module's own tree, refilled: matrices at 1/sqrt(fan_in), q_b_proj
    and kv_a_proj three times that (a flat softmax hides a fault of the
    scores), norm scales scattered about 1, adapters' B not zero."""
    model = CausalTransformer(cfg)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    )
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rs = np.random.RandomState(seed)
    out = []
    for path, leaf in leaves:
        names = [getattr(k, "key", "") for k in path]
        if names[-1] == "scale":
            x = 1.0 + 0.2 * rs.randn(*leaf.shape)
        elif names[-1] == "embedding":
            x = rs.randn(*leaf.shape)
        else:  # [in, out] kernels, adapters and [E, in, out] expert stacks
            x = rs.randn(*leaf.shape) / np.sqrt(leaf.shape[-2])
            if names[-1] == "kernel" and names[-2] in ("q_b_proj", "kv_a_proj"):
                x = 3.0 * x
            if names[-1] == "lora_b":  # a trained adapter: a tenth of its matrix
                x = 0.1 * x
        out.append(jnp.asarray(x, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def batch(seed, rows=B, width=T):
    """Left-padded rows: row ``i`` has ``8 * i`` padding tokens."""
    rs = np.random.RandomState(seed)
    ids = rs.randint(3, CFG.vocab_size - 3, (rows, width))
    mask = np.ones((rows, width), np.int32)
    for i in range(rows):
        mask[i, : 8 * i] = 0
    return jnp.asarray(ids, jnp.int32), jnp.asarray(mask)


def rel_l2(got, want, mask):
    m = np.asarray(mask, np.float64)[..., None]
    got, want = np.asarray(got, np.float64) * m, np.asarray(want, np.float64) * m
    return float(np.sqrt(((got - want) ** 2).sum() / (want**2).sum()))


def system_logits(params, ids, mask, cfg=CFG):
    return CausalTransformer(cfg).apply({"params": params}, ids, attention_mask=mask)["logits"]


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------


LORA = dataclasses.replace(CFG, lora_r=4, lora_alpha=8.0, lora_targets=("q_a_proj", "q_b_proj", "kv_a_proj", "o_proj"))


@pytest.mark.parametrize("cfg", [CFG, HELD, LORA], ids=["all_experts", "experts_2_and_3", "adapters"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_matches_reference(seed, cfg):
    params, (ids, mask) = seeded_params(seed, cfg), batch(seed)
    out = CausalTransformer(cfg).apply({"params": params}, ids, attention_mask=mask)
    want = reference.logits(params, dims_of(cfg), ids, mask, (0, T))
    assert rel_l2(out["logits"], want, mask) < TOL
    load, shared = np.asarray(out["router_load"]), np.asarray(out["router_shared"])
    assert load[0] == 0.0  # nothing dropped: the layers have no capacity
    assert load.shape == ((5,) if cfg is HELD else (2,))  # [.., held_frac, held_load_max_over_mean, compact_frac]
    # the shared expert's rows over its rows and the routed rows computed here
    # (all of them: 1 / (1 + top 2); a quarter of them held: about 1 / 1.5)
    assert shared.shape == (2,) and 0.0 < shared[1] < 1.0
    if cfg is HELD:
        assert 0.05 < load[2] < 0.6 and 1 / 3 < shared[0] < 1.0
    else:
        np.testing.assert_allclose(shared[0], 1 / 3, rtol=1e-6)


def test_left_padded_row_is_the_row_alone():
    params, (ids, mask) = seeded_params(3), batch(3)
    alone = system_logits(params, ids[2:, 16:], mask[2:, 16:])
    assert rel_l2(system_logits(params, ids, mask)[2:, 16:], alone, mask[2:, 16:]) < 1e-5


@pytest.mark.parametrize("fault", reference.FAULTS + (reference.PRECISION_CONTROL,))
def test_planted_fault_moves_the_logits(fault):
    params, (ids, mask) = seeded_params(5), batch(5)
    dims = dims_of(CFG)
    clean = reference.logits(params, dims, ids, mask, (0, T))
    moved = rel_l2(reference.logits(params, dims, ids, mask, (0, T), fault=fault), clean, mask)
    assert moved > 100 * TOL, (fault, moved)
    assert rel_l2(system_logits(params, ids, mask), clean, mask) < TOL


def test_hydra_branch_replays_both_kinds_of_layer():
    """The branch over the last block is a sparse layer; over all three it
    starts at the dense one: each replay is the full forward's top."""
    params, (ids, mask) = seeded_params(4), batch(4)
    model = CausalTransformer(CFG)
    for branch_layer in (1, 3):
        full = model.apply({"params": params}, ids, attention_mask=mask, branch_layer=branch_layer)
        top = model.apply({"params": params}, full["branch_input"], branch_layer, mask,
                          method=CausalTransformer.forward_branch)
        assert rel_l2(top["logits"], full["logits"], mask) < 1e-6


def test_flash_path_agrees_with_the_einsum_path():
    """The expanded form through the flash kernel (interpret mode), q/k heads
    of 24 beside v heads of 16."""
    params, (ids, mask) = seeded_params(1), batch(1)
    flash = dataclasses.replace(CFG, attention_impl="pallas")
    assert rel_l2(system_logits(params, ids, mask, flash), system_logits(params, ids, mask), mask) < TOL


# ---------------------------------------------------------------------------
# the sampler's cache: the latent, and the absorbed form
# ---------------------------------------------------------------------------


def test_cache_tree_holds_the_latent_and_no_k_or_v():
    cache = jax.eval_shape(lambda: make_kv_cache(CFG, B, T))
    assert [sorted(layer) for layer in cache] == [["ckv", "k_rope"]] * 3
    assert cache[0]["ckv"].shape == (B, T, 16) and cache[0]["k_rope"].shape == (B, T, 8)
    big = config_from_spec("builtin:pangu-ultra-moe-718b", num_layers=5, dtype=jnp.bfloat16)
    cache = jax.eval_shape(lambda: make_kv_cache(big, 64, 640))
    per_slot = sum(leaf.shape[-1] for leaf in cache[0].values())
    assert per_slot == 576 and all("k" not in layer and "v" not in layer for layer in cache)
    assert cache_bytes(cache, 640) == {LATENT: 5 * 64 * 640 * 1152}
    # per-head K and V of the same rows: 71 times as much
    assert 2 * 128 * (192 + 128) // 1152 == 71
    assert CFG.layer_layouts == (LayerLayout(None, True, "dense"),) + (LayerLayout(None, True, "moe"),) * 2
    assert CFG.mixed_layout and CFG.dims_per_head == 24 and CFG.v_dims_per_head == 16


@pytest.mark.parametrize("cfg", [CFG, LORA], ids=["plain", "adapters"])
@pytest.mark.parametrize("prompt", [5, 21])
def test_prefill_then_decode_through_the_latent_cache_matches_reference_full_forward(prompt, cfg):
    """The prompt's expanded prefill leaves its latents in the cache; then
    one token at a time in the absorbed form, to 40: logits, not tokens, at
    every position, against the reference's expanded full forward."""
    params, (ids, mask) = seeded_params(6, cfg), batch(6)
    model = CausalTransformer(cfg)
    want = reference.logits(params, dims_of(cfg), ids, mask, (0, T))
    slots = jnp.concatenate([mask[:, :prompt], jnp.zeros((B, T - prompt), jnp.int32)], axis=1)
    step = jax.jit(lambda ids_, slots_, cache_, at: model.apply(
        {"params": params}, ids_, attention_mask=slots_, cache=cache_, cache_index=at,
        kv_extents=(24, 32, 40)))
    out = model.apply({"params": params}, ids[:, :prompt], attention_mask=slots,
                      cache=make_kv_cache(cfg, B, T), cache_index=jnp.asarray(0, jnp.int32))
    assert rel_l2(out["logits"], want[:, :prompt], mask[:, :prompt]) < TOL
    for t in range(prompt, T):
        slots = slots.at[:, t].set(mask[:, t])
        out = step(ids[:, t : t + 1], slots, out["cache"], jnp.asarray(t))
        assert [sorted(layer) for layer in out["cache"]] == [["ckv", "k_rope"]] * 3
        assert rel_l2(out["logits"], want[:, t : t + 1], mask[:, t : t + 1]) < TOL, t


def test_absorbed_step_equals_the_expanded_pass_on_the_same_cache():
    """One function, two forms: the last position of an expanded pass over 17
    tokens and an absorbed step on the cache the first 16 left."""
    params, (ids, mask) = seeded_params(7), batch(7, width=17)
    mask = jnp.ones_like(mask)
    model = CausalTransformer(CFG)
    expanded = model.apply({"params": params}, ids, attention_mask=mask)["logits"][:, -1]
    slots = mask.at[:, 16].set(0)
    pre = model.apply({"params": params}, ids[:, :16], attention_mask=slots,
                      cache=make_kv_cache(CFG, B, 17), cache_index=jnp.asarray(0, jnp.int32))
    absorbed = model.apply({"params": params}, ids[:, 16:], attention_mask=mask, cache=pre["cache"],
                           cache_index=jnp.asarray(16, jnp.int32))["logits"][:, 0]
    assert float(jnp.max(jnp.abs(absorbed - expanded))) < 1e-4 * float(jnp.max(jnp.abs(expanded)))


def test_decode_step_builds_no_per_head_keys_or_values():
    """The compiled single-token step's temporaries stay under what per-head
    K and V of the rows would take (the expanded cache this model cannot
    hold): 16 heads, 8 rows of 512 slots."""
    cfg = dataclasses.replace(CFG, num_heads=16, num_layers=2, max_position_embeddings=1024)
    model = CausalTransformer(cfg)
    rows, slots = 8, 512
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    cache = jax.eval_shape(lambda: make_kv_cache(cfg, rows, slots))
    step = jax.jit(lambda p, i, m, c, at: model.apply(
        {"params": p}, i, attention_mask=m, cache=c, cache_index=at)["logits"])
    compiled = step.lower(params, jax.ShapeDtypeStruct((rows, 1), jnp.int32),
                          jax.ShapeDtypeStruct((rows, slots), jnp.int32), cache,
                          jax.ShapeDtypeStruct((), jnp.int32)).compile()
    expanded_one_layer = rows * slots * cfg.num_heads * (cfg.dims_per_head + cfg.v_dims_per_head) * 4
    assert compiled.memory_analysis().temp_size_in_bytes < expanded_one_layer / 2
    text = compiled.as_text()
    assert f"{rows},{slots},{cfg.num_heads},{cfg.dims_per_head}" not in text


def test_generate_records_the_references_logprobs(monkeypatch):
    """``generate()`` itself, sampling at temperature 1 from a 21-token
    left-padded prompt for 19 steps with a bucket of 4 slots: every step reads
    the latent through extents of 24, 28, ..., 40 slots, and the logprob the
    sampler recorded for each token is the reference's on the finished row."""
    monkeypatch.setattr(sampling, "KV_BUCKET", 4)
    params, (ids, mask) = seeded_params(8), batch(8)
    P, N = 21, T - 21
    model = CausalTransformer(CFG)
    seen = []

    def noting(p, i, **kw):
        seen.append(kw.get("kv_extents"))
        return model.apply({"params": p}, i, **kw)

    config = GenerationConfig(max_new_tokens=N, eos_token_id=None, pad_token_id=0)
    out = jax.jit(lambda r: generate(noting, params, lambda b, s: make_kv_cache(CFG, b, s),
                                     ids[:, :P], mask[:, :P], r, config))(jax.random.PRNGKey(1))
    assert seen[-1] == (24, 28, 32, 36, 40)
    full_mask = jnp.concatenate([mask[:, :P], out.response_mask], axis=1)
    want = reference.logits(params, dims_of(CFG), out.sequences, full_mask, (P - 1, T - 1))
    want_lp = jnp.take_along_axis(jax.nn.log_softmax(want), out.response_tokens[..., None], axis=-1)[..., 0]
    assert float(jnp.max(jnp.abs(want_lp - out.response_logprobs))) < 1e-4


# ---------------------------------------------------------------------------
# what a latent cache refuses, by name
# ---------------------------------------------------------------------------

LATENT_REFUSAL = (r"{path} does not support a model whose cache holds a latent in place of K and V \(leaves \['ckv', 'k_rope'\]\): "
                  r".*per-head K and V.*B4[ab]\); use the plain sampler")


def cache_of(cfg):
    return lambda B, S: make_kv_cache(cfg, B, S)


def build_slot_refill(paged):
    from trlx_tpu.ops.paged_kv import PagedSpec
    from trlx_tpu.ops.slot_refill import make_slot_refill_fns

    make_slot_refill_fns(
        None, cache_of(CFG), 2, 8, GenerationConfig(max_new_tokens=4, per_row_rng=True),
        paged=PagedSpec(block_size=2, max_blocks=8) if paged else None)


def build_prefix_cache():
    from trlx_tpu.engine.core import ContinuousEngine
    from trlx_tpu.ops.paged_kv import PagedKV, PagedSpec

    pool = PagedKV(pool=make_kv_cache(CFG, 8, 16), block_table=jnp.zeros((2, 3), jnp.int32))
    fns = types.SimpleNamespace(
        init_state=lambda: types.SimpleNamespace(cache=pool), batch_size=2, prompt_len=4,
        max_new_tokens=2, paged=PagedSpec(block_size=16, max_blocks=8), speculative=0)
    ContinuousEngine(fns, None, 0, prewarm=False, prefix_cache=True)


def sample_speculatively():
    from trlx_tpu.ops.speculative import generate_speculative

    ids = jnp.ones((2, 8), jnp.int32)
    generate_speculative(
        None, None, None, None, cache_of(CFG), cache_of(TransformerConfig.gpt2("test")),
        ids, ids, jax.random.PRNGKey(0), GenerationConfig(max_new_tokens=2))


@pytest.mark.parametrize("build,path", [
    (lambda: build_slot_refill(paged=False), "slot_refill"),
    (lambda: build_slot_refill(paged=True), "engine"),
    (build_prefix_cache, "prefix_cache"),
    (sample_speculatively, "speculative"),
], ids=["slot_refill", "engine", "prefix_cache", "speculative"])
def test_kv_only_path_refuses_a_latent_cache_by_name(build, path):
    with pytest.raises(NotImplementedError, match="^" + LATENT_REFUSAL.format(path=path)):
        build()
    refuse(jax.eval_shape(lambda: make_kv_cache(TransformerConfig.gpt2("test"), 2, 8)), path, 8)


@pytest.mark.parametrize("how", ["vector_cache_index", "span_past_slot_zero"])
def test_model_refuses_what_it_cannot_write_into_a_latent_cache(how):
    params, (ids, mask) = seeded_params(2), batch(2)
    cache = make_kv_cache(CFG, B, T)
    at = {"vector_cache_index": jnp.full((B,), 12, jnp.int32), "span_past_slot_zero": 12}[how]
    with pytest.raises(NotImplementedError, match="latent cache"):
        CausalTransformer(CFG).apply({"params": params}, ids[:, 12:14], attention_mask=mask,
                                     cache=cache, cache_index=at)


def test_ring_attention_refuses_latent_attention_by_name(monkeypatch):
    from trlx_tpu.models import transformer

    monkeypatch.setattr(transformer, "_maybe_ring_mesh", lambda T: object())
    params, (ids, mask) = seeded_params(2), batch(2)
    with pytest.raises(NotImplementedError, match="ring attention.*latent attention"):
        system_logits(params, ids, mask, dataclasses.replace(CFG, attention_impl="pallas"))


def test_scan_layers_refuses_two_kinds_of_layer_by_name():
    scanned = dataclasses.replace(CFG, scan_layers=True)
    with pytest.raises(NotImplementedError, match="scan_layers.*'pangu_ultra_moe'.*feed-forward kind"):
        CausalTransformer(scanned).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_kv_b_proj_takes_no_adapter():
    adapted = dataclasses.replace(LORA, lora_targets=LORA.lora_targets + ("kv_b_proj",))
    with pytest.raises(ValueError, match="kv_b_proj takes no LoRA adapter.*folds"):
        CausalTransformer(adapted).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_hf_interop_says_there_is_no_converter():
    from trlx_tpu.models.hf_interop import config_from_hf

    with pytest.raises(ValueError, match="pangu_ultra_moe.*no HF checkpoint conversion"):
        config_from_hf(types.SimpleNamespace(model_type="pangu_ultra_moe"))


# ---------------------------------------------------------------------------
# one chip's share of the experts, the shared expert counted once
# ---------------------------------------------------------------------------


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips hold experts 0-1, 2-3, 4-5, 6-7 of one layer, the same
    router and the same shared expert. Each routes over all eight,
    renormalises over both chosen, scales by 2.5 and computes its own
    experts' part, and the shared expert whole. The routed parts and ONE
    shared part sum to what the uncut reference gives for the whole layer."""
    rs = np.random.RandomState(11)
    d, f, E, K = CFG.hidden_size, CFG.expert_width, CFG.num_experts, CFG.num_experts_per_tok
    dense = lambda a, b: {"kernel": jnp.asarray(rs.randn(a, b) / np.sqrt(a), jnp.float32)}
    whole = {
        "router": {"kernel": jnp.asarray(rs.randn(d, E), jnp.float32)},
        "shared_expert": {"gate_proj": dense(d, f), "up_proj": dense(d, f), "down_proj": dense(f, d)},
        **{name: jnp.asarray(rs.randn(*shape) / np.sqrt(shape[-2]), jnp.float32)
           for name, shape in (("w_gate", (E, d, f)), ("w_up", (E, d, f)), ("w_down", (E, f, d)))},
    }
    n = jnp.asarray(rs.randn(B, T, d), jnp.float32)
    _, mask = batch(0)
    scaling = CFG.routed_scaling_factor
    routed_want, shared_want = reference.moe_layer(whole, n, K, scaling)

    routed_total, held_assignments = 0.0, 0.0
    for first in range(0, E, 2):
        share = dataclasses.replace(CFG, moe_experts_held=2, moe_first_expert=first)
        mine = {"router": whole["router"], "shared_expert": whole["shared_expert"],
                **{k: whole[k][first : first + 2] for k in ("w_gate", "w_up", "w_down")}}
        y, aux = MoEMLP(share).apply({"params": mine}, n, mask)
        routed_part, shared_part = reference.moe_layer(mine, n, K, scaling, first=first)
        assert rel_l2(y, routed_part + shared_part, mask) < TOL
        routed_total = routed_total + (y - shared_part)  # every chip computes the shared expert alike
        held_assignments += float(aux[6])
        assert float(aux[3]) == 0.0 and aux.shape == (12,)
        assert float(aux[10]) == float(jnp.sum(mask))  # rows through the shared expert, behind the two compact-call counts
    assert rel_l2(routed_total + shared_want, routed_want + shared_want, mask) < TOL
    assert held_assignments == float(jnp.sum(mask)) * K
    y_all, aux_all = MoEMLP(CFG).apply({"params": whole}, n, mask)
    assert rel_l2(y_all, routed_want + shared_want, mask) < TOL and aux_all.shape == (8,)


# ---------------------------------------------------------------------------
# the preset and the configuration file
# ---------------------------------------------------------------------------

PUBLISHED = {  # the catalog row's `config`, by TransformerConfig field
    "hidden_size": 7680, "intermediate_size": 18432, "moe_intermediate_size": 2048, "expert_width": 2048,
    "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "dims_per_head": 192, "v_dims_per_head": 128, "num_heads": 128, "kv_heads": 128,
    "num_experts": 256, "num_experts_per_tok": 8, "num_shared_experts": 1, "first_k_dense": 3,
    "moe_renormalize": True, "routed_scaling_factor": 2.5, "sandwich_norm": True, "num_layers": 61,
    "layer_norm_epsilon": 1e-5, "rope_theta": 25600000.0, "max_position_embeddings": 131072,
    "tie_word_embeddings": False, "vocab_size": 153600, "activation": "silu", "attn_bias": False,
    "model_type": "pangu_ultra_moe", "moe_scoring": "sigmoid",
}


@pytest.mark.parametrize("field", sorted(PUBLISHED))
def test_preset_holds_the_published_value(field):
    big = config_from_spec("builtin:pangu-ultra-moe-718b")
    assert getattr(big, field) == PUBLISHED[field]
    assert hash(big) == hash(config_from_spec("builtin:pangu-ultra-moe-718b"))


def test_the_cut_is_the_configuration_files_and_its_widths_check():
    from chipbench import job
    from trlx_tpu.data.configs import ModelConfig, ParallelConfig

    big = config_from_spec("builtin:pangu-ultra-moe-718b")
    assert [l.ffn for l in big.layer_layouts[:5]] == ["dense"] * 3 + ["moe"] * 2
    file = job.load_config("pangu-ultra-moe-718b-l5e8")
    model = file["job"]["model"]
    cut = config_from_spec(model["model_path"], **model["model_extra_kwargs"])
    assert (cut.num_layers, cut.first_k_dense, cut.experts_held, cut.num_experts, cut.vocab_size) == (
        5, 1, 8, 256, 19200)
    assert [l.ffn for l in cut.layer_layouts] == ["dense"] + ["moe"] * 4
    assert file["published"]["n_routed_experts"] == 8 and file["router_width"] == 256
    assert sorted(file["reduced"]) == ["first_k_dense_replace", "n_routed_experts", "num_hidden_layers",
                                       "num_nextn_predict_layers", "vocab_size"]
    assert model["peft_kwargs"]["modified_modules"] == ["q_a_proj", "q_b_proj", "kv_a_proj", "o_proj"]
    cfg = types.SimpleNamespace(model=ModelConfig(**model), parallel=ParallelConfig(**file["job"]["parallel"]))
    job.check_published_widths(cfg, file)
    # 3409 M parameters at this cut (the configuration file's arithmetic)
    shapes = jax.eval_shape(lambda: CausalTransformer(cut).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert abs(n / 1e6 - 3409) < 2


def test_collection_counters_name_the_latent_cache():
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.trainer.ppo import PPOTrainer

    cfg = default_ppo_config().evolve(
        tokenizer=dict(tokenizer_path="builtin:bytes"), train=dict(tracker=None),
        model=dict(model_path="builtin:pangu-test", num_layers_unfrozen=1),
        parallel=dict(param_dtype="float32", compute_dtype="float32"))
    trainer = PPOTrainer(cfg, reward_fn=lambda samples, **kw: [0.0] * len(samples))
    trainer._note_dense_kv_gauge((3, 21), GenerationConfig(max_new_tokens=19))
    assert trainer.last_cache_stats == {
        "rollout/kv_cache_bytes": 0.0, "rollout/ssm_state_bytes": 0.0, "rollout/kv_lane_heads": 1.0,
        "rollout/latent_cache_bytes": float(3 * 3 * 40 * (16 + 8) * 4),
        "rollout/index_cache_bytes": 0.0}, trainer.last_cache_stats  # no learned selection: no index keys
    assert trainer.last_kv_layers == ((40, False),) * 3


# ---------------------------------------------------------------------------
# trlx_tpu.train(): the normal PPO path with adapters, and GRPO
# ---------------------------------------------------------------------------


def _train_config(base, tmp_path, **model):
    return base.evolve(
        train=dict(seq_length=32, batch_size=4, total_steps=2, eval_interval=10,
                   checkpoint_interval=10, epochs=1, save_best=False, tracker=None,
                   checkpoint_dir=str(tmp_path / "ckpts"), logging_dir=str(tmp_path / "logs")),
        model=dict(model_path="builtin:pangu-test",
                   model_extra_kwargs=dict(moe_experts_held=2, moe_first_expert=2), **model),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        parallel=dict(param_dtype="float32", compute_dtype="float32"),
    )


def _run(config, **kw):
    import trlx_tpu.trlx as trlx

    records, before = [], {}

    def hook(trainer):
        trainer.tracker = types.SimpleNamespace(
            log=lambda stats, step=None: records.append(dict(stats)), finish=lambda: None)
        before.update(params=jax.tree_util.tree_map(np.asarray, trainer.state.params))

    rng = np.random.RandomState(0)
    prompts = ["".join(chr(97 + c) for c in rng.randint(0, 26, size=20)) for _ in range(8)]
    trainer = trlx.train(
        reward_fn=lambda samples, prompts, outputs, **kw: [float(i % 4) for i, _ in enumerate(outputs)],
        prompts=prompts, config=config, init_trainer_hook=hook, **kw)
    return trainer, records, before["params"]


def test_train_runs_ppo_with_adapters_and_only_they_and_the_value_head_change(tmp_path):
    """``trlx_tpu.train()`` with PPO, a value head, the hydra branch over the
    last block and LoRA on the four adaptable projections: the same trainer,
    collector, sampler, scoring forward and train step as every preset.
    Policy and branch start at KL 0; after two steps the last block's
    adapters and the value head have changed and nothing else has."""
    from trlx_tpu.data.default_configs import default_ppo_config

    config = _train_config(
        default_ppo_config(), tmp_path, num_layers_unfrozen=1,
        peft_kwargs=dict(peft_type="lora", r=4, lora_alpha=8,
                         modified_modules=["q_a_proj", "q_b_proj", "kv_a_proj", "o_proj"]),
    ).evolve(method=dict(num_rollouts=8, chunk_size=8, ppo_epochs=1,
                         gen_kwargs=dict(max_new_tokens=12, min_new_tokens=12, top_k=0, top_p=1.0, do_sample=True)))
    trainer, records, before = _run(config)
    assert trainer.tcfg.model_type == "pangu_ultra_moe" and trainer.tcfg.lora_r == 4
    collection = next(r for r in records if "time/exp" in r)
    assert float(collection.get("policy/sqrt_kl", collection.get("policy/sqrt_ref_kl"))) < 1e-6
    assert collection["rollout/kv_cache_bytes"] == 0.0
    S = int(collection["rollout/latent_cache_bytes"] // (3 * 8 * (16 + 8) * 4))
    assert 32 <= S <= 40 and collection["rollout/latent_cache_bytes"] == 3 * 8 * S * (16 + 8) * 4
    step = next(r for r in records if "time/train_step" in r)
    assert 0.0 < float(step["moe/held_frac"]) < 0.7 and float(step["moe/dropped_frac"]) == 0.0
    assert 1 / 3 < float(step["moe/shared_row_frac"]) < 1.0 and 0.0 < float(step["moe/chosen_score_mean"]) < 1.0
    assert np.isfinite([v for k, v in step.items() if k.startswith("losses/")]).all()
    changed = set()
    after = jax.tree_util.tree_map(np.asarray, trainer.state.params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(after), jax.tree_util.tree_leaves(before)):
        if not np.array_equal(a, b):
            changed.add(jax.tree_util.keystr(path))
    assert changed and all("v_head" in k or ("['h_2']" in k and "lora_" in k) for k in changed), changed
    assert any("lora_b" in k for k in changed) and any("v_head" in k for k in changed)
    adapted = {jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(after)
               if "lora_" in jax.tree_util.keystr(path)}
    assert adapted and not any("kv_b_proj" in k for k in adapted)


def test_train_runs_grpo_on_the_preset(tmp_path):
    from trlx_tpu.data.default_configs import default_grpo_config

    config = _train_config(default_grpo_config(), tmp_path, num_layers_unfrozen=1).evolve(
        method=dict(num_rollouts=8, chunk_size=8, group_size=4, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=12, min_new_tokens=12, top_k=0, top_p=1.0, do_sample=True)))
    trainer, records, before = _run(config)
    assert trainer.tcfg.model_type == "pangu_ultra_moe" and trainer.tcfg.experts_held == 2
    step = next(r for r in records if "time/train_step" in r)
    assert np.isfinite([v for k, v in step.items() if k.startswith("losses/")]).all()
    assert 0.0 < float(step["moe/held_frac"]) < 0.7
    collection = next(r for r in records if "time/exp" in r)
    assert collection["rollout/latent_cache_bytes"] > 0 and collection["rollout/kv_cache_bytes"] == 0.0
