"""dots3-note-prev (``model_type`` ``dots3_note``): latent attention of TWO
geometries in one stack (full layers under a learned selection of keys, each
from an indexer of its own; window layers at the ``swa_*`` sizes whose cache is
a ring of latents), a sigmoid gate a head, the normed latents rescaled, over
held experts, against the plain float32 reference the benchmark keeps
(``chipbench/reference/dots3_note.py``) at toy widths on the CPU.

``builtin:dots3-note-test``: dense full, full, window, window, window, full;
hidden 64; a full layer 4 heads, latents 32 / 16, q/k 20 = 12 + 8, v 16, rope
base 8e7; a window layer 2 heads, latents 24 / 24, q/k 24 = 20 + 4, v 12, rope
base 5e4; a window of 5, ``index_topk`` 8 (2 index heads of 12), 8 experts of
32 top-2 and a shared one. Rows of 40 tokens, so that the window AND the
selection bind in every comparison and a decode wraps the ring seven times.
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

from chipbench import flops
from chipbench.costs import dots3_note as costs
from chipbench.reference import dots3_note as reference
from trlx_tpu.models import transformer
from trlx_tpu.models.transformer import CausalTransformer, MoEMLP, TransformerConfig, config_from_spec, make_kv_cache
from trlx_tpu.ops.cache_layout import INDEX, LATENT, cache_bytes, ring
from trlx_tpu.ops.sampling import GenerationConfig, kv_slots_read, layer_extents

TOL = 1e-4  # relative L2 of float32 logits: what is left is the order of summation

F32 = dict(param_dtype=jnp.float32, dtype=jnp.float32)
CFG = config_from_spec("builtin:dots3-note-test", attention_impl="xla", **F32)
HELD = dataclasses.replace(CFG, moe_experts_held=2, moe_first_expert=2)  # one chip's share: experts 2 and 3 of the router's 8
LORA = dataclasses.replace(CFG, lora_r=4, lora_alpha=8.0, lora_targets=("q_a_proj", "q_b_proj", "kv_a_proj", "o_proj"))
B, T = 3, 40
FULL, WINDOW = (0, 1, 5), (2, 3, 4)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def dims_of(cfg):
    """The published keys the reference reads, from the config under test."""
    full, swa = cfg.attention_sizes(0), cfg.attention_sizes(2)
    return {
        "hidden_size": cfg.hidden_size, "num_hidden_layers": cfg.num_layers, "rms_norm_eps": cfg.layer_norm_epsilon,
        "num_attention_heads": full.heads, "qk_nope_head_dim": full.nope, "qk_rope_head_dim": full.rope,
        "v_head_dim": full.v, "rope_theta": full.theta,
        "swa_num_attention_heads": swa.heads, "swa_qk_nope_head_dim": swa.nope, "swa_qk_rope_head_dim": swa.rope,
        "swa_v_head_dim": swa.v, "swa_rope_theta": swa.theta, "sliding_window_size": swa.window,
        "layer_types": cfg.layer_types, "attention_gate_type": cfg.attention_gate_type,
        "apply_mla_qkv_lora_rescale": cfg.mla_lora_rescale,
        "index_topk": cfg.index_topk, "index_n_heads": cfg.index_heads, "index_head_dim": cfg.index_head_dim,
        "num_experts_per_tok": cfg.num_experts_per_tok, "routed_scaling_factor": cfg.routed_scaling_factor,
        "n_routed_experts": cfg.experts_held, "moe_first_expert_held": cfg.moe_first_expert, "lora_alpha": cfg.lora_alpha,
    }


def seeded_params(seed, cfg=CFG):
    """The module's own tree, refilled: matrices at 1/sqrt(fan_in), q_b_proj
    and kv_a_proj twice that (a flat softmax hides which keys a query kept),
    norm scales scattered about 1, the index key's LayerNorm bias and the
    router's selection bias not zero, adapters' B not zero."""
    shapes = jax.eval_shape(lambda: CausalTransformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rs = np.random.RandomState(seed)
    out = []
    for path, leaf in leaves:
        names = [getattr(k, "key", "") for k in path]
        if names[-1] == "scale":
            x = 1.0 + 0.2 * rs.randn(*leaf.shape)
        elif names[-1] == "embedding":
            x = rs.randn(*leaf.shape)
        elif names[-1] in ("bias", "router_bias"):
            x = 0.1 * rs.randn(*leaf.shape)
        else:  # [in, out] kernels, adapters and [E, in, out] expert stacks
            x = rs.randn(*leaf.shape) / np.sqrt(leaf.shape[-2])
            if names[-1] == "kernel" and names[-2] in ("q_b_proj", "kv_a_proj"):
                x = 2.0 * x
            if names[-1] == "lora_b":  # a trained adapter: a tenth of its matrix
                x = 0.1 * x
        out.append(jnp.asarray(x, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def batch(seed, rows=B, width=T, side="left"):
    """Row ``i`` has ``8 * i`` padding tokens, in front (the sampler's rows) or behind."""
    rs = np.random.RandomState(seed)
    ids = rs.randint(3, CFG.vocab_size - 3, (rows, width))
    mask = np.ones((rows, width), np.int32)
    for i in range(rows):
        if i:
            mask[i, : 8 * i] = 0 if side == "left" else 1
            if side == "right":
                mask[i, -8 * i :] = 0
    return jnp.asarray(ids, jnp.int32), jnp.asarray(mask)


def rel_l2(got, want, mask):
    m = np.asarray(mask, np.float64)[..., None]
    got, want = np.asarray(got, np.float64) * m, np.asarray(want, np.float64) * m
    return float(np.sqrt(((got - want) ** 2).sum() / (want**2).sum()))


def system_logits(params, ids, mask, cfg=CFG):
    return CausalTransformer(cfg).apply({"params": params}, ids, attention_mask=mask)["logits"]


# ---------------------------------------------------------------------------
# the forward pass: both geometries, the window and the selection binding
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [CFG, HELD, LORA], ids=["all_experts", "experts_2_and_3", "adapters"])
@pytest.mark.parametrize("side", ["left", "right"])
@pytest.mark.parametrize("seed", [0, 1])
def test_forward_matches_reference(seed, side, cfg):
    """Rows of 40, 32 and 24 real tokens: all longer than the window of 5 and
    than ``index_topk`` 8, padded in front or behind."""
    params, (ids, mask) = seeded_params(seed, cfg), batch(seed, side=side)
    want = reference.logits(params, dims_of(cfg), ids, mask, (0, T))
    assert rel_l2(system_logits(params, ids, mask, cfg), want, mask) < TOL


def test_left_padded_row_is_the_row_alone():
    params, (ids, mask) = seeded_params(3), batch(3)
    alone = system_logits(params, ids[2:, 16:], mask[2:, 16:])
    assert rel_l2(system_logits(params, ids, mask)[2:, 16:], alone, mask[2:, 16:]) < TOL


@pytest.mark.parametrize("fault", reference.FAULTS + (reference.PRECISION_CONTROL, reference.SOFTMAX_PRECISION_CONTROL))
def test_planted_fault_moves_the_logits(fault):
    """Every fault the chip's yardstick plants is visible in float32 on the
    CPU, where every expert is held; the controls for precision move them less
    than any fault of the mathematics and more than summation order does."""
    params, (ids, mask) = seeded_params(4), batch(4)
    clean = reference.logits(params, dims_of(CFG), ids, mask, (0, T))
    moved = rel_l2(reference.logits(params, dims_of(CFG), ids, mask, (0, T), fault=fault), clean, mask)
    assert moved > (1e-5 if fault == reference.SOFTMAX_PRECISION_CONTROL else 5e-3), moved


def test_flash_path_agrees_with_the_einsum_path():
    """The kernels (interpreted here) under ``window=`` at a window layer's
    unlike q/k and v sizes and under ``selection=`` at a full layer's."""
    params, (ids, mask) = seeded_params(5), batch(5)
    flash = system_logits(params, ids, mask, dataclasses.replace(CFG, attention_impl="pallas"))
    assert rel_l2(flash, system_logits(params, ids, mask), mask) < TOL


def test_flash_backward_at_unlike_sizes_with_a_window():
    """Forward and backward of the kernel at q/k 24 and v 12 under a window
    of 5 against the plain oracle: the shapes a window layer's pass has."""
    from trlx_tpu.ops.flash_attention import attention_reference, flash_attention

    rs = np.random.RandomState(0)
    q, k = (jnp.asarray(rs.randn(2, 40, 2, 24), jnp.float32) for _ in range(2))
    v = jnp.asarray(rs.randn(2, 40, 2, 12), jnp.float32)
    mask = jnp.ones((2, 40), jnp.int32).at[0, :7].set(0)
    loss = lambda fn: lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))
    kernel = loss(lambda q, k, v: flash_attention(q, k, v, mask, window=5, block_q=8, block_k=8))
    oracle = loss(lambda q, k, v: attention_reference(q, k, v, mask, window=5)[0] * mask[:, :, None, None])
    got, want = jax.grad(kernel, argnums=(0, 1, 2))(q, k, v), jax.grad(oracle, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape and float(jnp.max(jnp.abs(g - w) * mask[:, :, None, None])) < 1e-4


@pytest.mark.parametrize("branch_layer", [1, 2, 4], ids=["from_a_full_layer", "from_a_window_layer", "over_all_window_layers"])
def test_hydra_branch_replays_both_kinds_of_layer(branch_layer):
    params, (ids, mask) = seeded_params(6), batch(6)
    model = CausalTransformer(CFG)
    out = model.apply({"params": params}, ids, attention_mask=mask, branch_layer=branch_layer)
    branch = model.apply({"params": params}, out["branch_input"], branch_layer, mask, method=model.forward_branch)
    assert rel_l2(branch["logits"], out["logits"], mask) < 1e-6


def test_a_swapped_pair_of_geometries_fails():
    """The two kinds are unlike in every size, so a tree built for the other
    layout (window layers where the full ones are) does not fit, and the
    reference run at the other kind's rope base or without the window moves."""
    params, (ids, mask) = seeded_params(7), batch(7)
    swapped = dataclasses.replace(CFG, sliding_window_layout=(1, 1, 0, 0, 0, 1))
    with pytest.raises(Exception, match="shape|size|Shape"):
        system_logits(params, ids, mask, swapped)
    dims = dims_of(CFG)
    other = dict(dims, layer_types=[{"full_attention": "sliding_attention", "sliding_attention": "full_attention"}[t]
                                    for t in dims["layer_types"]])
    with pytest.raises(ValueError, match="layer_types says"):
        reference.logits(params, other, ids, mask, (0, T))


def test_attention_sizes_is_the_one_answer():
    full, swa = CFG.attention_sizes(0), CFG.attention_sizes(3)
    assert full == transformer.AttentionSizes(4, 32, 16, 12, 8, 16, 8e7, None)
    assert swa == transformer.AttentionSizes(2, 24, 24, 20, 4, 12, 5e4, 5)
    assert [CFG.layer_layout(i).indexer for i in range(6)] == ["full", "full", None, None, None, "full"]
    glm = TransformerConfig.glm("test")
    assert glm.attention_sizes(1) == transformer.AttentionSizes(4, 32, 16, 12, 8, 16, glm.rope_theta, None)
    gpt2 = TransformerConfig.gpt2("test")
    assert gpt2.attention_sizes(0)[:6] == (4, 0, 0, 16, 0, 16)
    assert CFG.layer_types == ["full_attention"] * 2 + ["sliding_attention"] * 3 + ["full_attention"]


# ---------------------------------------------------------------------------
# the sampler's caches: a ring of latents, a selecting cache
# ---------------------------------------------------------------------------


def test_cache_tree_holds_a_ring_on_window_layers_and_index_keys_on_full_ones():
    cache = jax.eval_shape(lambda: make_kv_cache(CFG, B, T))
    for i in FULL:
        assert {k: (v.shape, v.dtype) for k, v in cache[i].items()} == {
            "latent": ((B, T, 16 + 8), jnp.float32), "k_index": ((B, T, 12), jnp.float32)}
    for i in WINDOW:
        assert {k: (v.shape, v.dtype) for k, v in cache[i].items()} == {
            "ckv": ((B, 5, 24), jnp.float32), "k_rope": ((B, 5, 4), jnp.float32)}
    # the account beside the arithmetic: three window layers' rings, three full layers' latents and index keys, nothing else
    assert cache_bytes(cache, T) == {ring(LATENT): 3 * B * 5 * 28 * 4, LATENT: 3 * B * T * 24 * 4, INDEX: 3 * B * T * 12 * 4}
    short = jax.eval_shape(lambda: make_kv_cache(CFG, B, 4))  # a row inside the window: no ring
    assert cache_bytes(short, 4)[ring(LATENT)] == 0 and short[2]["ckv"].shape == (B, 4, 24)
    bf16 = jax.eval_shape(lambda: make_kv_cache(dataclasses.replace(CFG, dtype=jnp.bfloat16), B, T))
    assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(bf16)} == {jnp.dtype(jnp.bfloat16)}


def decode_through_the_caches(params, ids, mask, prompt, cfg=CFG, extents=None):
    """Prefill ``prompt`` tokens, then one token a step: logits ``[B, T, V]``."""
    model = CausalTransformer(cfg)
    slots = jnp.concatenate([mask[:, :prompt], jnp.zeros((B, T - prompt), jnp.int32)], axis=1)
    out = model.apply({"params": params}, ids[:, :prompt], attention_mask=slots, cache=make_kv_cache(cfg, B, T), cache_index=0)
    logits, cache = [out["logits"]], out["cache"]
    for t in range(prompt, T):
        slots = slots.at[:, t].set(mask[:, t])
        out = model.apply({"params": params}, ids[:, t : t + 1], attention_mask=slots, cache=cache, cache_index=t, kv_extents=extents)
        logits.append(out["logits"])
        cache = out["cache"]
    return jnp.concatenate(logits, axis=1), cache


@pytest.mark.parametrize("cfg", [CFG, LORA], ids=["plain", "adapters"])
@pytest.mark.parametrize("prompt,extents", [(3, None), (21, None), (21, (24, 32, 40))],
                         ids=["prompt_inside_the_ring", "prompt_wraps_the_ring", "three_extents"])
def test_prefill_then_decode_through_the_ring_and_the_selecting_cache(prompt, extents, cfg):
    """A prompt of 3 tokens fills part of the ring and decodes past seven
    wraps of it; a prompt of 21 leaves its last 5 latents there (rolled to
    their places) and selects from its first pass on. Every step's logits
    are the reference's full forward's at that position."""
    params, (ids, mask) = seeded_params(8, cfg), batch(8)
    mask = mask.at[1, :8].set(1).at[2, :16].set(1).at[1, :2].set(0).at[2, :1].set(0)  # short pads: real tokens in a prompt of 3
    got, cache = decode_through_the_caches(params, ids, mask, prompt, cfg, extents)
    want = reference.logits(params, dims_of(cfg), ids, mask, (0, T))
    real = mask.at[:, :prompt].set(mask[:, :prompt])
    assert rel_l2(got, want, real) < TOL
    assert cache[2]["ckv"].shape == (B, 5, 24) and float(jnp.abs(cache[0]["k_index"]).sum()) > 0


def test_the_ring_holds_the_last_window_of_scaled_latents():
    """After a prefill of 23 tokens ring position ``j`` holds slot 18 + ((j -
    3) mod 5); after a step at slot 23 position 3 holds it. What is held is the
    SCALED normed latent: ``sqrt(64 / 24)`` times a unit-RMS vector under norm
    scales of 1."""
    params, (ids, mask) = seeded_params(9), batch(9, rows=B)
    params = jax.tree_util.tree_map_with_path(
        lambda p, x: jnp.ones_like(x) if getattr(p[-1], "key", "") == "scale" and "kv_a_norm" in str(p) else x, params)
    mask = jnp.ones_like(mask)
    model = CausalTransformer(CFG)
    slots = jnp.zeros((B, T), jnp.int32).at[:, :23].set(1)
    out = model.apply({"params": params}, ids[:, :23], attention_mask=slots, cache=make_kv_cache(CFG, B, T), cache_index=0)
    ring = out["cache"][2]["ckv"]
    rms = jnp.sqrt(jnp.mean(ring**2, axis=-1))
    np.testing.assert_allclose(np.asarray(rms), np.sqrt(64 / 24), rtol=1e-4)
    step = model.apply({"params": params}, ids[:, 23:24], attention_mask=slots.at[:, 23].set(1), cache=out["cache"], cache_index=23)
    after = step["cache"][2]["ckv"]
    changed = np.asarray(jnp.any(after != ring, axis=(0, 2)))
    assert changed.tolist() == [j == 23 % 5 for j in range(5)]
    # the prefill's own order: slot 18..22 at positions 3, 4, 0, 1, 2
    whole = model.apply({"params": params}, ids[:, :23], attention_mask=jnp.ones((B, 23), jnp.int32),
                        cache=make_kv_cache(dataclasses.replace(CFG, sliding_window=64), B, 23), cache_index=0)
    # (a window of 64 keeps every latent of layer 2 in order; layer 2's INPUT is the same: layers 0 and 1 are full)
    np.testing.assert_allclose(np.asarray(ring[:, [3, 4, 0, 1, 2]]), np.asarray(whole["cache"][2]["ckv"][:, 18:23]), atol=1e-5)


def test_window_layers_read_their_ring_and_no_more():
    """Host arithmetic behind ``rollout/kv_window_read_frac``: a window
    layer's step reads its ring whole, whatever the row's extents."""
    extents = (7296, 7424, 7552, 7680, 7808, 7936, 8064, 8192)
    assert layer_extents(extents, 513) == (513,)
    assert kv_slots_read(layer_extents(extents, 513), 7168, 1024, 2048) == 1024 * 513
    assert kv_slots_read(layer_extents(extents, 8192), 7168, 1024, 2048) == 1024 * 2048


# ---------------------------------------------------------------------------
# what is refused, by name
# ---------------------------------------------------------------------------


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
def test_kv_only_path_refuses_the_latent_ring_and_the_index_cache_by_name(build, path):
    words = (rf"^{path} does not support a model whose cache holds a latent in place of K and V \(leaves \['latent'\]\): .*B4[ab]\); and a latent in "
             r"place of K and V in a ring of 5 slots for a row of \d+ \(leaves \['ckv', 'k_rope'\]\): .*B4[ab]\); and index keys beside a latent "
             r"\(leaves \['k_index'\]\): .*B8c\); use the plain sampler")
    with pytest.raises(NotImplementedError, match=words):
        build()


@pytest.mark.parametrize("how", ["vector_cache_index", "span_past_slot_zero"])
def test_model_refuses_what_it_cannot_write_into_a_latent_cache(how):
    params, (ids, mask) = seeded_params(2), batch(2)
    at = {"vector_cache_index": jnp.full((B,), 12, jnp.int32), "span_past_slot_zero": 12}[how]
    with pytest.raises(NotImplementedError, match="latent cache"):
        CausalTransformer(CFG).apply({"params": params}, ids[:, 12:14], attention_mask=mask, cache=make_kv_cache(CFG, B, T), cache_index=at)
    with pytest.raises(NotImplementedError, match="latent cache is written at one scalar cache_index"):
        CausalTransformer(CFG).apply({"params": params}, ids[:, 12:13], attention_mask=mask, cache=make_kv_cache(CFG, B, T),
                                     cache_index=jnp.full((B,), 12, jnp.int32))


@pytest.mark.parametrize("target,words", [
    ("kv_b_proj", "kv_b_proj takes no LoRA adapter.*folds"),
    ("head_gate", "head_gate takes no LoRA adapter.*one number a head"),
    ("wq_b", "indexer takes no LoRA adapter.*no gradient"),
    ("wk", "indexer takes no LoRA adapter.*no gradient"),
    ("weights_proj", "indexer takes no LoRA adapter.*no gradient"),
])
def test_kv_b_proj_the_gate_and_the_indexer_take_no_adapter(target, words):
    adapted = dataclasses.replace(LORA, lora_targets=LORA.lora_targets + (target,))
    with pytest.raises(ValueError, match=words):
        CausalTransformer(adapted).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


@pytest.mark.parametrize("change,words", [
    (dict(sliding_window_layout=None), "learned selection.*beside a sliding window only where sliding_window_layout leaves layers full"),
    (dict(indexer_types=("full",) * 6), "learned selection.*no indexer_types"),
    (dict(attention_gate_type="elementwise"), "attention_gate_type 'elementwise'"),
    (dict(kv_lora_rank=0, index_topk=0, sliding_window=None), "swa_.*take latent attention"),
    (dict(scan_layers=True), None),
], ids=["every_layer_windowed", "borrowing_across_windows", "another_gate", "swa_sizes_without_a_latent", "scan_layers"])
def test_config_refuses_what_is_not_built(change, words):
    if words is None:
        with pytest.raises(NotImplementedError, match=r"scan_layers.*'dots3_note'.*more than one attention layout"):
            CausalTransformer(dataclasses.replace(CFG, **change)).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    else:
        with pytest.raises(ValueError, match=words):
            dataclasses.replace(CFG, **change)


@pytest.mark.parametrize("way", ["import", "export"])
def test_hf_interop_says_there_is_no_converter(way):
    from trlx_tpu.models.hf_interop import UnsupportedHFExport, config_from_hf, hf_config_from_transformer

    if way == "import":
        with pytest.raises(ValueError, match="dots3_note.*no HF checkpoint conversion.*B4"):
            config_from_hf(types.SimpleNamespace(model_type="dots3_note"))
    else:
        with pytest.raises(UnsupportedHFExport, match="dots3_note.*no HF checkpoint conversion"):
            hf_config_from_transformer(CFG)


def test_ring_attention_refuses_latent_attention_by_name(monkeypatch):
    monkeypatch.setattr(transformer, "_maybe_ring_mesh", lambda T: object())
    params, (ids, mask) = seeded_params(2), batch(2)
    with pytest.raises(NotImplementedError, match="ring attention.*latent attention"):
        system_logits(params, ids, mask, dataclasses.replace(CFG, attention_impl="pallas"))


# ---------------------------------------------------------------------------
# one chip's share of the experts, the shared expert counted once
# ---------------------------------------------------------------------------


def test_the_four_shares_add_up_to_the_uncut_layer():
    rs = np.random.RandomState(11)
    d, f, E, K = CFG.hidden_size, CFG.expert_width, CFG.num_experts, CFG.num_experts_per_tok
    dense = lambda a, b: {"kernel": jnp.asarray(rs.randn(a, b) / np.sqrt(a), jnp.float32)}
    whole = {
        "router": {"kernel": jnp.asarray(rs.randn(d, E), jnp.float32)},
        "router_bias": jnp.asarray(0.3 * rs.randn(E), jnp.float32),
        "shared_expert": {"gate_proj": dense(d, f), "up_proj": dense(d, f), "down_proj": dense(f, d)},
        **{name: jnp.asarray(rs.randn(*shape) / np.sqrt(shape[-2]), jnp.float32)
           for name, shape in (("w_gate", (E, d, f)), ("w_up", (E, d, f)), ("w_down", (E, f, d)))},
    }
    n = jnp.asarray(rs.randn(B, T, d), jnp.float32)
    _, mask = batch(0)
    scaling = CFG.routed_scaling_factor
    assert scaling == 1.0
    routed_want, shared_want = reference.moe_layer(whole, n, K, scaling)
    routed_total = 0.0
    for first in range(0, E, 2):
        share = dataclasses.replace(CFG, moe_experts_held=2, moe_first_expert=first)
        mine = {k: whole[k] for k in ("router", "router_bias", "shared_expert")}
        mine.update({k: whole[k][first : first + 2] for k in ("w_gate", "w_up", "w_down")})
        y, _ = MoEMLP(share).apply({"params": mine}, n, mask)
        routed_part, shared_part = reference.moe_layer(mine, n, K, scaling, first=first)
        assert rel_l2(y, routed_part + shared_part, mask) < TOL
        routed_total = routed_total + (y - shared_part)  # every chip computes the shared expert alike
    assert rel_l2(routed_total + shared_want, routed_want + shared_want, mask) < TOL


# ---------------------------------------------------------------------------
# the preset, the configuration file, the counts
# ---------------------------------------------------------------------------

PUBLISHED = {  # the catalog row's `config`, by TransformerConfig field or property
    "hidden_size": 5120, "intermediate_size": 13824, "moe_intermediate_size": 1536, "num_layers": 46, "vocab_size": 152064,
    "num_heads": 128, "kv_heads": 128, "q_lora_rank": 1024, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "rope_theta": 8e7,
    "swa_num_heads": 64, "swa_q_lora_rank": 1024, "swa_kv_lora_rank": 1024, "swa_qk_nope_head_dim": 192,
    "swa_qk_rope_head_dim": 64, "swa_v_head_dim": 128, "swa_rope_theta": 5e4, "sliding_window": 513,
    "attention_gate_type": "headwise", "mla_lora_rescale": True,
    "index_topk": 2048, "index_heads": 64, "index_head_dim": 128, "num_experts": 256, "num_experts_per_tok": 8,
    "num_shared_experts": 1, "first_k_dense": 1, "moe_renormalize": True, "routed_scaling_factor": 1.0,
    "moe_scoring": "sigmoid", "moe_topk_method": "noaux_tc", "layer_norm_epsilon": 1e-5, "max_position_embeddings": 524288,
    "tie_word_embeddings": False, "activation": "silu", "attn_bias": False, "model_type": "dots3_note",
}


@pytest.mark.parametrize("field", sorted(PUBLISHED))
def test_preset_holds_the_published_value(field):
    assert getattr(config_from_spec("builtin:dots3-note"), field) == PUBLISHED[field]


def test_the_cut_is_the_configuration_files_and_its_widths_check():
    from chipbench import job
    from trlx_tpu.data.configs import ModelConfig, ParallelConfig

    big = config_from_spec("builtin:dots3-note")
    file = job.load_config("dots3-note-prev-l6e8")
    with open(CATALOG) as f:
        catalog = next(row for row in map(json.loads, f) if row["name"] == "dots3-note-prev")
    assert big.layer_types == catalog["config"]["layer_types"] and sum(l.window is None for l in big.layer_layouts) == 13
    for key, value in catalog["config"].items():  # every number of the catalog's config under the same key, but the reduced ones
        if key not in file["reduced"]:
            assert file["published"][key] == value, key
    entry = next(c for c in job.load_benchmark()["configs"] if c["name"] == "dots3-note-prev-l6e8")
    assert sorted(file["reduced"]) == sorted(entry["reduced"]) == ["layer_types", "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert entry["source"] == catalog["source_url"] == file["source"]
    assert {k for k in catalog["config"] if k.startswith("swa_")} <= set(file["maps"])
    model = file["job"]["model"]
    cut = config_from_spec(model["model_path"], **model["model_extra_kwargs"])
    assert (cut.num_layers, cut.experts_held, cut.num_experts, cut.vocab_size) == (6, 8, 256, 19008)
    assert cut.layer_types == file["published"]["layer_types"] == catalog["config"]["layer_types"][:6]
    assert [(l.ffn, l.indexer, l.window) for l in cut.layer_layouts] == [
        ("dense", "full", None), ("moe", "full", None), ("moe", None, 513), ("moe", None, 513), ("moe", None, 513), ("moe", "full", None)]
    assert model["peft_kwargs"]["modified_modules"] == ["q_a_proj", "q_b_proj", "kv_a_proj", "o_proj"]
    assert {"attention_gate_type", "apply_mla_qkv_lora_rescale", "window", "router", "init_stds", "not_built"} <= set(file["assumed"])
    assert "32 chips" in file["deployment"]
    cfg = types.SimpleNamespace(model=ModelConfig(**model), parallel=ParallelConfig(**file["job"]["parallel"]))
    job.check_published_widths(cfg, file)
    shapes = jax.eval_shape(lambda: CausalTransformer(cut).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree)) / 1e6
    assert abs(count(shapes) - 2179.9) < 1.0  # the configuration file's arithmetic
    assert abs(count(shapes["h_1"]["attn"]) - (134.69 + 9.37)) < 0.05 and abs(count(shapes["h_2"]["attn"]) - 90.83) < 0.05
    assert sorted(k for k in shapes if k.startswith("h_") and "indexer" in shapes[k]["attn"]) == ["h_0", "h_1", "h_5"]
    cache = jax.eval_shape(lambda: make_kv_cache(dataclasses.replace(cut, dtype=jnp.bfloat16), 8, 8192))
    assert cache_bytes(cache, 8192)[ring(LATENT)] == 3 * 8 * 513 * 1088 * 2
    assert cache[1]["latent"].shape == (8, 8192, 576) and cache[1]["k_index"].shape == (8, 8192, 128)
    traffic = job.load_json("traffic", "ppo_ctx8k_u2")
    assert traffic["job"]["model"]["num_layers_unfrozen"] == 2 and set(traffic["job"]) == {"method", "train", "model"}


NEW_METRICS = ("latent_ring_gib", "latent_ring_step_device_ms", "latent_ring_step_roofline", "window_latent_pass_device_ms",
               "window_latent_pass_roofline", "select_step_device_ms", "select_step_roofline")
# not `index_cache_gib` and `attn_selected_pct`, whose counters the cell logs too: the benchmark's own test pins both
# lists to cell 8 (chipbench/tests/test_glm_costs.py; PERF.md section 7 has the edit for a `benchmark` issue)
APPENDED_TO = ("latent_cache_gib", "sparse_gather_rows", "attn_visited_pct", "kv_window_read_pct",
               "moe_held_pct", "moe_held_imbalance", "moe_share_gmm_device_ms", "moe_gmm_roofline")


@pytest.mark.parametrize("name", NEW_METRICS + APPENDED_TO)
def test_the_cells_metrics_are_declared_and_their_files_name_what_the_harness_finds(name):
    """Each new metric lists the new cell alone and agrees with its file; each
    accepted metric the cell joins lists it (last when PR 56 appended it; a
    later cell is appended behind it), and its file is the accepted one (a reducer the harness has, a
    key the program logs or a pattern that compiles, a cost function the
    family's file or ``flops.py`` brings)."""
    from chipbench import job, layers

    cell = "dots3note_ppo_ctx8k"
    entry = next(m for m in job.load_benchmark()["per_layer"] if m["name"] == name)
    spec = layers.metric_files()[name]
    assert all(entry[k] == spec[k] for k in ("unit", "better", "source", "layer", "moves"))
    assert cell in entry["workloads"] and (entry["workloads"] == [cell]) == (name in NEW_METRICS)
    if "pattern" in spec:
        re.compile(spec["pattern"])
        assert "PATTERN" not in spec["pattern"] + spec["reads"]
    if "costs" in spec:
        model = types.SimpleNamespace(family=flops.family_module("dots3_note"))
        assert callable(flops.kernel_costs(spec["costs"], model))
    if "key" in spec:
        source = open(os.path.join(os.path.dirname(transformer.__file__), "..", "trainer", "base.py")).read() + \
            open(os.path.join(os.path.dirname(transformer.__file__), "..", "trainer", "ppo.py")).read()
        assert f'"{spec["key"]}"' in source


def test_costs_layer_forward_equals_the_references_matmuls_in_both_kinds_of_layer():
    """``costs/dots3_note.py::layer_forward`` against a count by hand of the
    reference's products at the toy sizes, a window layer and a full one."""
    shapes = jax.eval_shape(lambda: CausalTransformer(HELD).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    t, d = 30, 64
    window = costs.layer_forward(HELD, 2, shapes["h_2"], t, {"moe/held_frac": 0.25})
    full = costs.layer_forward(HELD, 1, shapes["h_1"], t, {"moe/held_frac": 0.25})
    assert window["mix"] == 2 * 2 * (24 + 12) * flops.pairs(t, 5)  # 2 heads of q/k 24, v 12 on the pairs inside the window
    attn = lambda a, b: 2 * a * b * t
    assert {p[1]: v for p, v in window["matmuls"].items() if p[0] == "attn"} == {
        "q_a_proj": attn(d, 24), "q_b_proj": attn(24, 2 * 24), "kv_a_proj": attn(d, 24 + 4), "kv_b_proj": attn(24, 2 * (20 + 12)),
        "o_proj": attn(2 * 12, d), "head_gate": attn(d, 2)}
    index = 2 * 2 * 12 * flops.pairs(t, None)  # two index heads of 12 on every causal pair
    projections = attn(32, 2 * 12) + attn(d, 12) + attn(d, 2)
    assert full["mix"] == 2 * 4 * (20 + 16) * flops.pairs(t, 8) - index - projections  # ... entered so that no backward is counted
    assert full["matmuls"][("attn", "indexer", "wq_b", "kernel")] == 2 * attn(32, 2 * 12) + 2 * index
    assert full["matmuls"][("attn", "q_b_proj", "kernel")] == attn(32, 4 * 20) and full["matmuls"][("attn", "head_gate", "kernel")] == attn(d, 4)
    assert full["matmuls"][("mlp", "w_up")] == window["matmuls"][("mlp", "w_up")] == 2 * d * 32 * 2 * 0.25 * t
    forward = sum(full["matmuls"].values()) + full["mix"]
    assert forward == sum(flops.generic_layer_forward(HELD, 1, shapes["h_1"], t, {"moe/held_frac": 0.25})["matmuls"].values()) \
        + 2 * 4 * 36 * flops.pairs(t, 8) + index
    model = types.SimpleNamespace(tcfg=HELD, n_layers=6, lowest_trained=4, ref_layers=[4, 5], epochs=1, act_bytes=4)
    cycle = {"row_lengths": [(30, 10)] * 2, "steps": [{}] * 2}
    ring = costs.latent_ring_step(model, cycle)
    assert ring == [{"phase": "decode", "flops": 3 * 2 * 9 * (2.0 * 2 * (28 + 24) * 5 + 2.0 * 2 * 24 * 32), "bytes": 3 * 2 * 9 * 4.0 * 28 * 5}]
    # the three full layers: 4 heads over the 8 chosen slots of 16 + 8, and two index heads of 12 over all of steps' 31 to 39 slots
    assert costs.select_step(model, cycle) == [{
        "phase": "decode", "flops": 3 * 2 * (9 * (2.0 * 4 * (24 + 16) * 8 + 2.0 * 4 * 16 * 28) + 2.0 * 2 * 12 * 315),
        "bytes": 3 * 2 * 4.0 * (9 * 24 * 8 + 12 * 315)}]
    glm = types.SimpleNamespace(tcfg=config_from_spec("builtin:glm-test"), n_layers=1, act_bytes=2)
    from chipbench.costs import glm_moe_dsa
    assert costs.select_row_step(glm.tcfg, 0, 40, 2) == glm_moe_dsa.sparse_decode_row_step(glm.tcfg, 40, True, 2)  # cell 8's count of a `full` layer
    passes = {p["phase"]: p for p in costs.window_latent_pass(model, cycle)}
    assert passes["prefill"]["flops"] == 3 * 2 * 2 * 2 * 36 * flops.pairs(30, 5)
    assert passes["score_reference"]["flops"] == 1 * 2 * 2 * 2 * 36 * flops.pairs(40, 5)  # layer 4 alone is a window layer
    assert passes["train_backward"]["flops"] == 2 * passes["score_reference"]["flops"]
    both = {p["phase"]: p for p in costs.flash_fwd(model, cycle)}
    assert both["score"]["flops"] == passes["score"]["flops"] + 3 * 2 * 2 * 4 * 36 * flops.pairs(40, 8)


# ---------------------------------------------------------------------------
# no existing program moves
# ---------------------------------------------------------------------------

RECORDED = os.path.join(os.path.dirname(__file__), "fixtures", "programs_before_dots3_note.json")
RECORDED_FAMILIES = ("smallthinker", "pangu", "glm", "k-exaone", "kimi-linear")  # the presets of cells 6, 7, 8, 9 and 11


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
    """Recorded on PR 56's parent by this function (``python
    tests/test_dots3_note.py`` there writes the file), before
    ``TransformerConfig`` gained ``attention_sizes`` and the ``swa_*`` sizes,
    ``LatentAttention`` its window, ring, gate and scales, ``_layer_plans``
    a plan a kind of latent layer and ``Block`` the gate's statistics:
    parameter tree, cache tree, train step and decode step byte for byte."""
    with open(RECORDED) as f:
        assert program_fingerprints(family) == json.load(f)[family]


# ---------------------------------------------------------------------------
# trlx_tpu.train(): the normal PPO path with adapters
# ---------------------------------------------------------------------------


def test_collection_counters_tell_the_ring_from_the_full_layers_latents():
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.trainer.ppo import PPOTrainer

    cfg = default_ppo_config().evolve(
        tokenizer=dict(tokenizer_path="builtin:bytes"), train=dict(tracker=None),
        model=dict(model_path="builtin:dots3-note-test", num_layers_unfrozen=2),
        parallel=dict(param_dtype="float32", compute_dtype="float32"))
    trainer = PPOTrainer(cfg, reward_fn=lambda samples, **kw: [0.0] * len(samples))
    trainer._note_dense_kv_gauge((3, 21), GenerationConfig(max_new_tokens=19))
    assert trainer.last_cache_stats == {
        "rollout/kv_cache_bytes": 0.0, "rollout/ssm_state_bytes": 0.0, "rollout/kv_lane_heads": 1.0,
        "rollout/latent_cache_bytes": float(3 * 3 * 40 * (16 + 8) * 4),
        "rollout/latent_ring_bytes": float(3 * 3 * 5 * (24 + 4) * 4),
        "rollout/index_cache_bytes": float(3 * 3 * 40 * 12 * 4),
        "rollout/sparse_gather_rows": 3.0 * 8}, trainer.last_cache_stats
    assert trainer.last_kv_layers == ((40, False), (40, False), (5, True), (5, True), (5, True), (40, False))


def test_train_runs_ppo_with_adapters_through_both_kinds_of_layer(tmp_path):
    """``trlx_tpu.train()`` with PPO, a value head, the hydra branch over the
    last TWO blocks (a window layer and a selecting one) and LoRA, rows of 48
    slots: policy and branch start at KL 0; after two steps the two unfrozen
    blocks' adapters and the value head have changed and nothing else has: not
    the gate, not the indexer; the records carry the ring, the window layers'
    reads and the mean gate."""
    import trlx_tpu.trlx as trlx
    from trlx_tpu.data.default_configs import default_ppo_config

    config = default_ppo_config().evolve(
        train=dict(seq_length=48, batch_size=4, total_steps=2, eval_interval=10,
                   checkpoint_interval=10, epochs=1, save_best=False, tracker=None,
                   checkpoint_dir=str(tmp_path / "ckpts"), logging_dir=str(tmp_path / "logs")),
        model=dict(model_path="builtin:dots3-note-test", num_layers_unfrozen=2,
                   model_extra_kwargs=dict(moe_experts_held=4),
                   peft_kwargs=dict(peft_type="lora", r=4, lora_alpha=8,
                                    modified_modules=["q_a_proj", "q_b_proj", "kv_a_proj", "o_proj"])),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        parallel=dict(param_dtype="float32", compute_dtype="float32"),
        method=dict(num_rollouts=8, chunk_size=8, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=12, min_new_tokens=12, top_k=0, top_p=1.0, do_sample=True)),
    )
    records, before = [], {}

    def hook(trainer):
        trainer.tracker = types.SimpleNamespace(
            log=lambda stats, step=None: records.append(dict(stats)), finish=lambda: None)
        before.update(params=jax.tree_util.tree_map(np.asarray, trainer.state.params))

    rng = np.random.RandomState(0)
    prompts = ["".join(chr(97 + c) for c in rng.randint(0, 26, size=36)) for _ in range(8)]
    trainer = trlx.train(
        reward_fn=lambda samples, prompts, outputs, **kw: [float(i % 4) for i, _ in enumerate(outputs)],
        prompts=prompts, config=config, init_trainer_hook=hook)
    assert trainer.tcfg.model_type == "dots3_note" and trainer.tcfg.lora_r == 4
    collection = next(r for r in records if "time/exp" in r)
    assert float(collection.get("policy/sqrt_kl", collection.get("policy/sqrt_ref_kl"))) < 1e-6
    S = 40 + 12  # the prompts' padded width and the new tokens
    assert collection["rollout/latent_ring_bytes"] == 3 * 8 * 5 * 28 * 4
    assert collection["rollout/latent_cache_bytes"] == 3 * 8 * S * 24 * 4 and collection["rollout/kv_cache_bytes"] == 0
    assert collection["rollout/index_cache_bytes"] == 3 * 8 * S * 12 * 4
    assert collection["rollout/kv_window_read_frac"] == pytest.approx(5 / S)
    step = next(r for r in records if "time/train_step" in r)
    assert 0.3 < step["learn/attn_gate_mean"] < 0.7
    assert step["learn/attn_selected_frac"] == pytest.approx(transformer.selected_frac(int(step["learn/step_width"]), 8))
    assert 0.0 < step["learn/attn_visited_frac"] <= 1.0 and 0.0 < step["moe/held_frac"] < 1.0
    assert np.isfinite([v for k, v in step.items() if k.startswith("losses/")]).all()
    changed = set()
    after = jax.tree_util.tree_map(np.asarray, trainer.state.params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(after), jax.tree_util.tree_leaves(before["params"])):
        if not np.array_equal(a, b):
            changed.add(jax.tree_util.keystr(path))
    assert changed and all("v_head" in k or (("['h_4']" in k or "['h_5']" in k) and "lora_" in k) for k in changed), changed
    assert any("['h_4']" in k for k in changed) and any("['h_5']" in k for k in changed) and any("v_head" in k for k in changed)


if __name__ == "__main__":  # the recorder
    with jax.default_matmul_precision(None):
        print(json.dumps({f: program_fingerprints(f) for f in RECORDED_FAMILIES}, indent=1))
