"""GLM-5.2 (latent attention under a learned selection of keys that a ``full``
layer's indexer makes and the ``shared`` layers behind it borrow, a leading
dense layer before sparse ones, a selection bias in the router, a shared
expert, a chip's share of the experts) against the plain float32 reference
the benchmark keeps, ``chipbench/reference/glm_moe_dsa.py``.

Toy size on the CPU (``builtin:glm-test``: 1 dense + 4 sparse layers whose
indexer types are full, shared, shared, shared, full; hidden 64, 4 heads whose
q/k are 20 = 12 + 8 and whose v is 16, latents of 32 and 16, 2 index heads of
12, ``index_topk`` 8, 8 experts of 32 top-2 and a shared one), float32 on both
sides, rows of 40 tokens so that the selection BINDS in every comparison: the
full forward; the sampler's prefill and its single-token steps through the
latent and the index cache against the reference's full forward; the hydra
branch from a full and from a shared layer; the PPO loss's gradient with
respect to the adapters; every planted fault; a row no longer than
``index_topk`` equal to plain latent attention; the four shares adding up;
PPO with LoRA through ``trlx_tpu.train()``; and each refusal by name.
"""

import dataclasses
import hashlib
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import glm_moe_dsa as reference
from trlx_tpu.models import transformer
from trlx_tpu.models.transformer import (
    CausalTransformer,
    MoEMLP,
    TransformerConfig,
    config_from_spec,
    largest_k,
    make_kv_cache,
    selected_frac,
    sparse_gather_rows,
)
from trlx_tpu.ops import sampling
from trlx_tpu.ops.cache_layout import INDEX, LATENT, cache_bytes, refuse
from trlx_tpu.ops.sampling import GenerationConfig, generate, kv_slots_read

# Relative L2 of the logits. Both sides compute in float32 on the CPU; what is
# left is the order of summation. The same keys are selected on both sides:
# index scores that differ in the last bit change a set only at an exact tie,
# and both sides break a tie toward the lower slot.
TOL = 1e-4

CFG = TransformerConfig.glm("test", param_dtype=jnp.float32, dtype=jnp.float32, attention_impl="xla")
# one chip's share: experts 2 and 3 of the router's 8
HELD = dataclasses.replace(CFG, moe_experts_held=2, moe_first_expert=2)
LORA = dataclasses.replace(CFG, lora_r=4, lora_alpha=8.0, lora_targets=("q_a_proj", "q_b_proj", "kv_a_proj", "o_proj"))
B, T = 3, 40
TYPES = ("full", "shared", "shared", "shared", "full")


def dims_of(cfg):
    return {
        "num_hidden_layers": cfg.num_layers,
        "first_k_dense_replace": cfg.first_k_dense,
        "num_attention_heads": cfg.num_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim,
        "rms_norm_eps": cfg.layer_norm_epsilon,
        "rope_parameters": {"rope_theta": cfg.rope_theta, "rope_type": "default"},
        "index_topk": cfg.index_topk,
        "index_n_heads": cfg.index_heads,
        "index_head_dim": cfg.index_head_dim,
        "indexer_types": list(cfg.indexer_types),
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "n_routed_experts": cfg.experts_held,
        "moe_first_expert_held": cfg.moe_first_expert,
        "lora_alpha": cfg.lora_alpha,
    }


def seeded_params(seed, cfg=CFG):
    """The module's own tree, refilled: matrices at 1/sqrt(fan_in), q_b_proj
    and kv_a_proj three times that (a flat softmax hides which keys a query
    kept), norm scales scattered about 1, the index key's LayerNorm bias and
    the router's selection bias not zero, adapters' B not zero."""
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
        elif names[-1] in ("bias", "router_bias"):
            x = 0.1 * rs.randn(*leaf.shape)
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


@pytest.mark.parametrize("cfg", [CFG, HELD, LORA], ids=["all_experts", "experts_2_and_3", "adapters"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_matches_reference(seed, cfg):
    params, (ids, mask) = seeded_params(seed, cfg), batch(seed)
    got = system_logits(params, ids, mask, cfg)
    want = reference.logits(params, dims_of(cfg), ids, mask, (0, T))
    assert rel_l2(got, want, mask) < TOL
    # and the selection binds: with every causal key the logits are others
    assert rel_l2(got, reference.logits(params, dims_of(cfg), ids, mask, (0, T), fault="dense_attention"), mask) > 30 * TOL


def test_left_padded_row_is_the_row_alone():
    params, (ids, mask) = seeded_params(3), batch(3)
    together = system_logits(params, ids, mask)
    alone = system_logits(params, ids[2:, 16:], mask[2:, 16:])
    np.testing.assert_allclose(together[2, 16:], alone[0], atol=2e-4)


@pytest.mark.parametrize("fault", reference.FAULTS + (reference.PRECISION_CONTROL,))
def test_planted_fault_moves_the_logits(fault):
    """Every expert is held here, so the router's faults show too."""
    params, (ids, mask) = seeded_params(4), batch(4)
    got = system_logits(params, ids, mask)
    assert rel_l2(got, reference.logits(params, dims_of(CFG), ids, mask, (0, T), fault=fault), mask) > 30 * TOL


def test_a_row_no_longer_than_index_topk_is_plain_latent_attention(monkeypatch):
    """``t < index_topk`` selects every causal key: the same tree under a
    selection that can never bind gives the same logits, bit for bit, and the
    program builds no selection at all (the flash or einsum path as it was)."""
    params, (ids, mask) = seeded_params(5), batch(5, rows=2, width=CFG.index_topk)
    long_ids, long_mask = batch(5, rows=2)
    never = dataclasses.replace(CFG, index_topk=1000)
    assert rel_l2(system_logits(params, long_ids, long_mask), system_logits(params, long_ids, long_mask, never), long_mask) > 30 * TOL

    def not_reached(*a, **kw):
        raise AssertionError("a row of index_topk tokens built a selection")

    monkeypatch.setattr(transformer, "select_keys", not_reached)
    monkeypatch.setattr(transformer, "selected_attention", not_reached)
    short = system_logits(params, ids, mask)
    assert np.array_equal(np.asarray(short), np.asarray(system_logits(params, ids, mask, never)))


@pytest.mark.parametrize("branch_layer", [1, 2, 5], ids=["from_a_full_layer", "from_a_shared_layer", "whole_stack"])
def test_hydra_branch_replays_the_selection(branch_layer):
    """The branch from layer 4 (full) makes its own selection; from layer 3
    (shared) it is handed layer 0's with the hidden states."""
    params, (ids, mask) = seeded_params(6), batch(6)
    model = CausalTransformer(CFG)
    out = model.apply({"params": params}, ids, attention_mask=mask, branch_layer=branch_layer)
    assert isinstance(out["branch_input"], tuple) == (branch_layer == 2)
    branch = model.apply({"params": params}, out["branch_input"], branch_layer, mask, method=CausalTransformer.forward_branch)
    assert rel_l2(branch["logits"], out["logits"], mask) < 1e-6
    assert rel_l2(branch["logits"], reference.logits(params, dims_of(CFG), ids, mask, (0, T)), mask) < TOL


def test_flash_path_agrees_with_the_einsum_path():
    """Under ``attention_impl: pallas`` a selecting pass reads its padding from
    the kernel's key mask and not from a bias; a short row runs the kernel."""
    params, (ids, mask) = seeded_params(7), batch(7)
    pallas = dataclasses.replace(CFG, attention_impl="pallas")
    assert rel_l2(system_logits(params, ids, mask, pallas), system_logits(params, ids, mask), mask) < 1e-5
    ids8, mask8 = ids[:, 32:], mask[:, 32:]
    assert rel_l2(system_logits(params, ids8, mask8, pallas), system_logits(params, ids8, mask8), mask8) < 1e-5


@pytest.mark.parametrize("k", [1, 7, 8, 40, 64])
def test_largest_k_is_a_stable_top_k(k):
    rs = np.random.RandomState(k)
    x = rs.randn(6, 40).astype(np.float32)
    x[1, ::3] = 0.0  # ties straddling the k-th place: the first by position count
    x[2] = -np.inf
    x[3, 5:] = -np.inf
    x[4] = np.abs(x[4]).round(1)
    want = np.zeros(x.shape, bool)
    order = np.argsort(-x, axis=-1, kind="stable")[:, : min(k, 40)]
    np.put_along_axis(want, order, True, axis=-1)
    assert np.array_equal(np.asarray(largest_k(jnp.asarray(x), k)), want)


def test_selected_frac_at_the_cells_width():
    assert selected_frac(8192, 2048) == pytest.approx(0.43748, abs=1e-5)  # attn_selected_pct 43.7
    assert selected_frac(2048, 2048) == 1.0 and selected_frac(8, 2048) == 1.0
    assert selected_frac(40, 8) == (36 + 32 * 8) / 820


# ---------------------------------------------------------------------------
# the sampler's caches
# ---------------------------------------------------------------------------


def test_cache_tree_holds_index_keys_on_full_layers_only():
    cache = jax.eval_shape(lambda: make_kv_cache(CFG, B, T))
    assert [sorted(layer) for layer in cache] == [
        ["k_index", "latent"] if kind == "full" else ["latent"] for kind in TYPES]
    assert cache[1]["latent"].shape == (B, T, CFG.kv_lora_rank + CFG.qk_rope_head_dim) == (B, T, 16 + 8)
    assert cache[0]["k_index"].shape == (B, T, CFG.index_head_dim)
    assert cache_bytes(cache, T) == {LATENT: 5 * B * T * (16 + 8) * 4, INDEX: 2 * B * T * 12 * 4}
    pangu = jax.eval_shape(lambda: make_kv_cache(TransformerConfig.pangu("test"), B, T))
    assert cache_bytes(pangu, T)[INDEX] == 0 and all(sorted(layer) == ["ckv", "k_rope"] for layer in pangu)


def decode_through_the_caches(params, ids, mask, prompt, cfg=CFG, spy=None):
    """Prefill ``prompt`` tokens, then one token a step: logits ``[B, T, V]``."""
    model = CausalTransformer(cfg)
    slots = jnp.concatenate([mask[:, :prompt], jnp.zeros((B, T - prompt), jnp.int32)], axis=1)
    out = model.apply({"params": params}, ids[:, :prompt], attention_mask=slots,
                      cache=make_kv_cache(cfg, B, T), cache_index=0)
    logits, cache = [out["logits"]], out["cache"]
    for t in range(prompt, T):
        slots = slots.at[:, t].set(mask[:, t])
        out = model.apply({"params": params}, ids[:, t : t + 1], attention_mask=slots, cache=cache, cache_index=t)
        logits.append(out["logits"])
        cache = out["cache"]
        if spy is not None:
            spy(t, cache)
    return jnp.concatenate(logits, axis=1), cache


@pytest.mark.parametrize("cfg", [CFG, LORA], ids=["plain", "adapters"])
@pytest.mark.parametrize("prompt", [5, 21])
def test_prefill_then_decode_through_both_caches_matches_reference_full_forward(prompt, cfg):
    """A prompt of 5 tokens prefills without a selection and decodes into
    one; a prompt of 21 selects from its first pass on. Either way every
    step's logits are the reference's full forward's at that position."""
    params, (ids, mask) = seeded_params(8, cfg), batch(8)
    mask = mask.at[:, :prompt].set(mask[:, :prompt]).at[2, :3].set(0).at[2, 3:].set(1)  # a short pad: real tokens in the prompt
    got, cache = decode_through_the_caches(params, ids, mask, prompt, cfg)
    want = reference.logits(params, dims_of(cfg), ids, mask, (0, T))
    assert rel_l2(got, want, mask) < TOL
    assert float(jnp.abs(cache[0]["k_index"]).sum()) > 0 and "k_index" not in cache[1]


def test_a_decode_step_past_index_topk_reads_the_borrowed_set_on_a_shared_layer(monkeypatch):
    """Layers 0 and 4 select (two calls a step); every layer's absorbed
    attention sees ``index_topk`` gathered slots, and layers 1 to 3 see
    exactly the slots layer 0 chose."""
    params, (ids, mask) = seeded_params(9), batch(9)
    chosen, attended = [], []
    select, absorbed = transformer.select_slots, transformer.absorbed_latent_attention

    def noting_select(*a, **kw):
        chosen.append(select(*a, **kw))
        return chosen[-1]

    def noting_absorbed(q_c, q_r, ckv, k_rope, *a, **kw):
        attended.append(jnp.concatenate([ckv, k_rope], axis=-1))
        return absorbed(q_c, q_r, ckv, k_rope, *a, **kw)

    monkeypatch.setattr(transformer, "select_slots", noting_select)
    monkeypatch.setattr(transformer, "absorbed_latent_attention", noting_absorbed)
    _, cache = decode_through_the_caches(params, ids, mask, T - 1)
    assert len(chosen) == 2 and len(attended) == 5
    assert all(c.shape == (B, CFG.index_topk) for c in chosen)
    assert all(a.shape == (B, CFG.index_topk, CFG.kv_lora_rank + CFG.qk_rope_head_dim) for a in attended)
    assert all(int(c.min()) >= 0 for c in chosen)  # every row has index_topk visible slots here
    assert not np.array_equal(np.sort(chosen[0]), np.sort(chosen[1]))
    for layer in (1, 2, 3):
        borrowed = jnp.take_along_axis(cache[layer]["latent"], chosen[0][:, :, None], axis=1)
        assert np.array_equal(np.asarray(attended[layer]), np.asarray(borrowed))
    own = jnp.take_along_axis(cache[4]["latent"], chosen[1][:, :, None], axis=1)
    assert np.array_equal(np.asarray(attended[4]), np.asarray(own))


PANGU = TransformerConfig.pangu("test", param_dtype=jnp.float32, dtype=jnp.float32, attention_impl="xla")


@pytest.mark.parametrize("prompt", [5, 21])
@pytest.mark.parametrize("cfg", [CFG, PANGU], ids=["glm", "pangu"])
def test_cached_steps_give_the_logits_of_the_cacheless_pass(cfg, prompt):
    """Both families, one leaf ``latent`` a layer under glm's selection and
    ``ckv`` beside ``k_rope`` without one: a prefill (two column ranges
    written) and then a slot a step, against the program's own expanded
    pass without a cache. Rows of 0, 8 and 16 pads on 40 slots: the glm steps
    select on every layer, and from a prompt of 5 a row's first steps see
    fewer than ``index_topk`` slots (picks of -1)."""
    params, (ids, mask) = seeded_params(11, cfg), batch(11)
    got, cache = decode_through_the_caches(params, ids, mask, prompt, cfg)
    assert rel_l2(got, system_logits(params, ids, mask, cfg), mask) < TOL
    r = cfg.kv_lora_rank
    assert all(sorted(layer) in (["latent"], ["k_index", "latent"]) if cfg.index_topk else sorted(layer) == ["ckv", "k_rope"] for layer in cache)
    for layer in cache:  # the latent and the roped key of every slot were written
        halves = (layer["latent"][0, :, :r], layer["latent"][0, :, r:]) if cfg.index_topk else (layer["ckv"][0], layer["k_rope"][0])
        assert all(float(jnp.abs(columns).sum(axis=-1).min()) > 0 for columns in halves)


def test_a_pick_of_minus_one_is_the_masked_slot_the_gathered_bias_kept_out(monkeypatch):
    """Row 2 is left-padded to slot 35: at the step that writes slot 38 it has
    three visible slots of a cache of 40, fewer than ``index_topk`` = 8. The
    absorbed attention of every layer equals the step's plain formula as it
    stood with THREE gathers a slot: ``top_k``'s own places (masked ones
    among them), the latent, the roped key and the bias each gathered by
    them, two score products and their sum."""
    params, (ids, mask) = seeded_params(12), batch(12)
    mask = mask.at[2, :36].set(0)
    selects, steps = [], []
    select, absorbed = transformer.select_slots, transformer.absorbed_latent_attention

    def noting_select(q_i, k_i, w, bias, ci, extents, topk):
        selects.append((q_i, k_i, w, bias))
        return select(q_i, k_i, w, bias, ci, extents, topk)

    def noting_absorbed(q_c, q_r, ckv, k_rope, bias, ci, extents, scale, dtype):
        steps.append((q_c, q_r, scale, absorbed(q_c, q_r, ckv, k_rope, bias, ci, extents, scale, dtype)))
        return steps[-1][-1]

    monkeypatch.setattr(transformer, "select_slots", noting_select)
    monkeypatch.setattr(transformer, "absorbed_latent_attention", noting_absorbed)

    model, prompt, r = CausalTransformer(CFG), 38, CFG.kv_lora_rank
    slots = jnp.concatenate([mask[:, :prompt], jnp.zeros((B, T - prompt), jnp.int32)], axis=1)
    out = model.apply({"params": params}, ids[:, :prompt], attention_mask=slots, cache=make_kv_cache(CFG, B, T), cache_index=0)
    slots = slots.at[:, prompt].set(1)
    out = model.apply({"params": params}, ids[:, prompt : prompt + 1], attention_mask=slots, cache=out["cache"], cache_index=prompt)
    assert len(selects) == 2 and len(steps) == 5

    def old_places(q_i, k_i, w, bias):  # select_slots as it was: top_k's places, whatever their values
        scores = transformer.index_scores(q_i[:, None], k_i, w[:, None])[:, 0]
        return jax.lax.top_k(jnp.where(bias[:, 0, 0] > -1.0, scores, -jnp.inf), CFG.index_topk)[1]

    picked = [select(*args, prompt, None, CFG.index_topk) for args in selects]
    assert [int((p[2] < 0).sum()) for p in picked] == [5, 5] and all(int(p[:2].min()) >= 0 for p in picked)
    for layer, (q_c, q_r, scale, got) in enumerate(steps):
        args = selects[layer == 4]  # layers 1 to 3 borrow layer 0's set
        places, bias = old_places(*args), args[3]
        assert np.array_equal(np.asarray(places)[:2], np.asarray(picked[layer == 4])[:2])
        latent = out["cache"][layer]["latent"]
        c, k_r = (jnp.take_along_axis(a, places[:, :, None], axis=1) for a in (latent[..., :r], latent[..., r:]))
        b = jnp.take_along_axis(bias, places[:, None, None, :], axis=3)[:, :, 0]
        assert sorted(np.unique(np.asarray(b)).tolist()) == [-1e9, 0.0]
        scores = jnp.einsum("bhr,bsr->bhs", q_c, c) + jnp.einsum("bhd,bsd->bhs", q_r, k_r)
        want = jnp.einsum("bhs,bsr->bhr", jax.nn.softmax(scores * scale + b, axis=-1), c)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_a_cache_no_longer_than_index_topk_reads_the_one_leaf_as_the_split_form():
    """On a cache of ``index_topk`` slots the selection never binds and every
    step attends densely over the ``latent`` leaf's two column ranges. The
    same steps over a cache that holds those ranges as ``ckv`` and ``k_rope``
    (the layout of a layer without an indexer) give the same logits and
    leave the same numbers in the slots."""
    slots, prompt, r = CFG.index_topk, 5, CFG.kv_lora_rank
    params, (ids, _) = seeded_params(13), batch(13, width=slots)
    mask = jnp.asarray(np.arange(slots)[None, :] >= 2 * np.arange(B)[:, None], jnp.int32)  # 0, 2 and 4 pads
    model = CausalTransformer(CFG)

    def split(cache):
        return [{"ckv": layer["latent"][..., :r], "k_rope": layer["latent"][..., r:],
                 **{name: leaf for name, leaf in layer.items() if name != "latent"}} for layer in cache]

    def run(cache):
        seen = jnp.concatenate([mask[:, :prompt], jnp.zeros((B, slots - prompt), jnp.int32)], axis=1)
        out = model.apply({"params": params}, ids[:, :prompt], attention_mask=seen, cache=cache, cache_index=0)
        logits = [out["logits"]]
        for t in range(prompt, slots):
            seen = seen.at[:, t].set(mask[:, t])
            out = model.apply({"params": params}, ids[:, t : t + 1], attention_mask=seen, cache=out["cache"], cache_index=t)
            logits.append(out["logits"])
        return jnp.concatenate(logits, axis=1), out["cache"]

    fused, fused_cache = run(make_kv_cache(CFG, B, slots))
    apart, apart_cache = run(split(make_kv_cache(CFG, B, slots)))
    assert all("latent" in layer for layer in fused_cache) and all("ckv" in layer and "latent" not in layer for layer in apart_cache)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(apart), rtol=1e-6, atol=1e-6)
    for one, two in zip(split(fused_cache), apart_cache):
        assert sorted(one) == sorted(two)
        for name in one:
            np.testing.assert_array_equal(np.asarray(one[name]), np.asarray(two[name]))
    assert rel_l2(fused, system_logits(params, ids, mask), mask) < TOL


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations carry
    (``cond`` branches, ``pjit`` and ``custom_jvp`` bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


@pytest.mark.parametrize("extents", [None, (24, 32, 40)], ids=["one_extent", "three_extents"])
def test_a_decode_step_gathers_one_row_a_chosen_slot_and_nothing_of_the_bias(extents):
    """The traced single-token step of the selecting model: one gather a
    latent layer, ``[B, S, r + dr] -> [B, index_topk, r + dr]``, none whose
    operand is the bias ``[B, 1, 1, S]`` or any other view of the cache, and
    the rows those gathers fetch are what ``rollout/sparse_gather_rows`` says."""
    model, width = CausalTransformer(CFG), CFG.kv_lora_rank + CFG.qk_rope_head_dim
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    cache = jax.eval_shape(lambda: make_kv_cache(CFG, B, T))
    kw = {"kv_extents": extents} if extents else {}
    step = lambda p, i, m, c, at: model.apply({"params": p}, i, attention_mask=m, cache=c, cache_index=at, **kw)["logits"]
    jaxpr = jax.make_jaxpr(step)(params, jax.ShapeDtypeStruct((B, 1), jnp.int32), jax.ShapeDtypeStruct((B, T), jnp.int32),
                                 cache, jax.ShapeDtypeStruct((), jnp.int32))
    gathers = [e for e in _equations(jaxpr.jaxpr) if e.primitive.name == "gather"]
    operands = [tuple(e.invars[0].aval.shape) for e in gathers]
    assert not [s for s in operands if len(s) == 4]  # the bias is [B, 1, 1, T]
    of_the_cache = [e for e in gathers if T in e.invars[0].aval.shape and len(e.invars[0].aval.shape) == 3]
    assert [tuple(e.invars[0].aval.shape) for e in of_the_cache] == [(B, T, width)] * CFG.num_layers
    rows = sum(int(np.prod(e.outvars[0].aval.shape[:-1])) // B for e in of_the_cache)
    assert all(e.outvars[0].aval.shape[-1] == width for e in of_the_cache)
    assert rows == sparse_gather_rows(CFG, T) == 5 * 8
    # a cache no longer than index_topk selects nothing, and a model without an indexer never does
    assert sparse_gather_rows(CFG, 8) == 0 and sparse_gather_rows(PANGU, T) == 0
    big = config_from_spec("builtin:glm-5.2", num_layers=5, first_k_dense=1, indexer_types=TYPES)
    assert sparse_gather_rows(big, 8192) == 10240  # the cell: five layers of 2048


@pytest.mark.parametrize("path", ["slot_refill", "engine", "prefix_cache", "speculative"])
@pytest.mark.parametrize("spec,rows,slots,latent,index", [
    ("builtin:glm-5.2", 8, 8192, 377487360, 33554432),  # latent_cache_gib 0.3516, index_cache_gib 0.03125
    ("builtin:pangu-ultra-moe-718b", 64, 640, 235929600, 0),  # latent_cache_gib 0.21973
], ids=["glm52_ppo_ctx8k", "pangu718b_ppo_decode"])
def test_the_cells_caches_hold_the_bytes_they_held_and_are_refused_as_they_were(spec, rows, slots, latent, index, path):
    """One leaf of 576 columns in place of 512 and 64 on a layer under a
    selection, the two leaves without one: the same 1152 bytes a slot a layer
    at both cells' shapes, found by leaf name, and every KV-only path still
    stops at either."""
    kw = dict(indexer_types=TYPES) if index else {}
    cfg = config_from_spec(spec, num_layers=5, first_k_dense=1, dtype=jnp.bfloat16, **kw)
    cache = jax.eval_shape(lambda: make_kv_cache(cfg, rows, slots))
    shapes = {"latent": (rows, slots, 576)} if index else {"ckv": (rows, slots, 512), "k_rope": (rows, slots, 64)}
    assert all({k: v.shape for k, v in layer.items() if k != "k_index"} == shapes for layer in cache)
    assert all(leaf.dtype == jnp.bfloat16 for leaf in jax.tree_util.tree_leaves(cache))
    held = cache_bytes(cache, slots)  # the account beside the arithmetic: 1152 bytes a slot a layer, 128 bf16 index keys on two
    assert (held[LATENT], held[INDEX], sum(held.values())) == (latent, index, latent + index)
    assert latent == 5 * rows * slots * 1152 and index == (2 * rows * slots * 128 * 2 if index else 0)
    leaves = r"\['latent'\]" if index else r"\['ckv', 'k_rope'\]"
    with pytest.raises(NotImplementedError, match=rf"^{path} does not support .*a latent in place of K and V \(leaves {leaves}\): .*B4[ab]\)"):
        refuse(cache, path, slots)
    stacked = jax.eval_shape(lambda: make_kv_cache(dataclasses.replace(cfg, scan_layers=True), rows, slots))
    assert {k: v.shape for k, v in stacked.items() if k != "k_index"} == {k: (5,) + v for k, v in shapes.items()}
    with pytest.raises(NotImplementedError, match=rf"^{path} does not support"):
        refuse(stacked, path, slots)


def test_generate_records_the_references_logprobs(monkeypatch):
    """``generate()`` itself, sampling at temperature 1 from a 21-token
    left-padded prompt for 19 steps with a bucket of 4 slots: the index pass
    reads extents of 24, 28, ..., 40 slots, the attention its eight chosen
    ones, and the logprob the sampler recorded for each token is the
    reference's on the finished row."""
    monkeypatch.setattr(sampling, "KV_BUCKET", 4)
    params, (ids, mask) = seeded_params(10), batch(10)
    mask = mask.at[2, :3].set(0).at[2, 3:].set(1)
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
    # a step on more than index_topk slots reads that many, whatever the extent
    assert kv_slots_read((24, 28, 32, 36, 40), P, N, selected=8) == 8 * N
    assert kv_slots_read((8,), 4, 4, selected=8) == kv_slots_read((8,), 4, 4) == 32


# ---------------------------------------------------------------------------
# the PPO loss's gradient with respect to the adapters
# ---------------------------------------------------------------------------


def test_ppo_loss_gradient_of_the_adapters_matches_the_references():
    """The clipped PPO objective on the response half of each row, through
    the program's forward and through the reference's: the adapters'
    gradients agree, and the indexer, behind ``top_k``, gets none on either
    side."""
    from trlx_tpu.data.default_configs import default_ppo_config

    method = default_ppo_config().method
    params, (ids, mask) = seeded_params(11, LORA), batch(11)
    R = T // 2
    rs = np.random.RandomState(11)
    adv = jnp.asarray(rs.randn(B, R), jnp.float32)
    old = jnp.asarray(-5.0 + 0.1 * rs.randn(B, R), jnp.float32)
    zeros = jnp.zeros((B, R), jnp.float32)

    def loss_of(logits_fn):
        def loss(p):
            lp = jax.nn.log_softmax(logits_fn(p)[:, T - R - 1 : T - 1])
            lp = jnp.take_along_axis(lp, ids[:, T - R :, None], axis=-1)[..., 0]
            return method.loss(lp, zeros, old + jax.lax.stop_gradient(lp + 5.0), zeros, adv, zeros, mask[:, T - R :])[0]

        return loss

    got = jax.grad(loss_of(lambda p: system_logits(p, ids, mask, LORA)))(params)
    want = jax.grad(loss_of(lambda p: reference.logits(p, dims_of(LORA), ids, mask, (0, T))))(params)
    compared = 0
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        if "indexer" in name:
            assert float(jnp.abs(g).max()) == 0.0 and float(jnp.abs(w).max()) == 0.0, name
        elif "lora_" in name:
            assert float(jnp.abs(w).max()) > 0.0, name
            assert float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)) < 1e-3, name
            compared += 1
    assert compared == 5 * 4 * 2


# ---------------------------------------------------------------------------
# what a selection and its cache refuse, by name
# ---------------------------------------------------------------------------

LATENT_REFUSAL = (r"{path} does not support a model whose cache holds a latent in place of K and V \(leaves \['latent'\]\): .*B4[ab]\); "
                  r"and index keys beside a latent \(leaves \['k_index'\]\): .*B8c\); use the plain sampler")


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
def test_kv_only_path_refuses_the_latent_and_the_index_cache_by_name(build, path):
    with pytest.raises(NotImplementedError, match="^" + LATENT_REFUSAL.format(path=path)):
        build()
    # a latent cache without index keys is refused for the latent alone
    with pytest.raises(NotImplementedError, match=r"cache holds a latent in place of K and V \(leaves \['ckv', 'k_rope'\]\): [^;]*B4[ab]\); use the plain sampler"):
        refuse(jax.eval_shape(lambda: make_kv_cache(TransformerConfig.pangu("test"), 2, 8)), path, 8)


@pytest.mark.parametrize("how", ["vector_cache_index", "span_past_slot_zero"])
def test_model_refuses_what_it_cannot_write_into_a_latent_cache(how):
    params, (ids, mask) = seeded_params(2), batch(2)
    cache = make_kv_cache(CFG, B, T)
    at = {"vector_cache_index": jnp.full((B,), 12, jnp.int32), "span_past_slot_zero": 12}[how]
    with pytest.raises(NotImplementedError, match="latent cache"):
        CausalTransformer(CFG).apply({"params": params}, ids[:, 12:14], attention_mask=mask,
                                     cache=cache, cache_index=at)


def test_ring_attention_refuses_latent_attention_by_name(monkeypatch):
    monkeypatch.setattr(transformer, "_maybe_ring_mesh", lambda T: object())
    params, (ids, mask) = seeded_params(2), batch(2)
    with pytest.raises(NotImplementedError, match="ring attention.*latent attention"):
        system_logits(params, ids, mask, dataclasses.replace(CFG, attention_impl="pallas"))


@pytest.mark.parametrize("stack", ["the_preset", "sparse_layers_only"])
def test_scan_layers_and_the_pipeline_refuse_a_selection_that_is_lent_by_name(stack):
    """The pipeline schedule runs the scanned stack, so this is its refusal
    too. Even a stack of one feed-forward kind is refused while a layer
    borrows another's selection: the scan's carry has no place for one."""
    cfg = dataclasses.replace(CFG, scan_layers=True)
    if stack == "sparse_layers_only":
        cfg = dataclasses.replace(cfg, first_k_dense=0)
    with pytest.raises(NotImplementedError, match=r"scan_layers \(and the pipeline schedule.*'glm_moe_dsa'.*indexer type.*"
                                                   r"no place for a selection of keys.*B8"):
        CausalTransformer(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_a_shared_layer_without_a_selection_says_so():
    params, (ids, mask) = seeded_params(2), batch(2)
    first_shared = dataclasses.replace(CFG, num_layers=1)
    object.__setattr__(first_shared, "indexer_types", ("shared",))
    tree = {k: v for k, v in params.items() if k != "h_0"} | {"h_0": params["h_1"] | {"mlp": params["h_0"]["mlp"]}}
    with pytest.raises(ValueError, match="`shared` was handed no selection"):
        system_logits(tree, ids, mask, first_shared)
    with pytest.raises(ValueError, match="the first full"):
        dataclasses.replace(CFG, indexer_types=("shared",) * 5)
    with pytest.raises(ValueError, match="learned selection.*latent cache"):
        dataclasses.replace(TransformerConfig.gpt2("test"), index_topk=8)


@pytest.mark.parametrize("target", ["kv_b_proj", "wq_b", "wk", "weights_proj"])
def test_kv_b_proj_and_the_indexer_take_no_adapter(target):
    adapted = dataclasses.replace(LORA, lora_targets=LORA.lora_targets + (target,))
    words = "kv_b_proj takes no LoRA adapter.*folds" if target == "kv_b_proj" else "indexer takes no LoRA adapter.*no gradient"
    with pytest.raises(ValueError, match=words):
        CausalTransformer(adapted).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


@pytest.mark.parametrize("way", ["import", "export"])
def test_hf_interop_says_there_is_no_converter(way):
    from trlx_tpu.models.hf_interop import UnsupportedHFExport, config_from_hf, hf_config_from_transformer

    if way == "import":
        with pytest.raises(ValueError, match="glm_moe_dsa.*no HF checkpoint conversion.*B8"):
            config_from_hf(types.SimpleNamespace(model_type="glm_moe_dsa"))
    else:
        with pytest.raises(UnsupportedHFExport, match="glm_moe_dsa.*no HF checkpoint conversion"):
            hf_config_from_transformer(CFG)


# ---------------------------------------------------------------------------
# one chip's share of the experts, the shared expert counted once
# ---------------------------------------------------------------------------


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips hold experts 0-1, 2-3, 4-5, 6-7 of one layer, the same
    router with the same selection bias and the same shared expert. Each
    chooses over all eight by score + bias, renormalises the two chosen
    SCORES, scales by 2.5 and computes its own experts' part, and the shared
    expert whole. The routed parts and ONE shared part sum to what the uncut
    reference gives for the whole layer; without the bias it gives another."""
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
    routed_want, shared_want = reference.moe_layer(whole, n, K, scaling)
    unbiased, _ = reference.moe_layer(whole, n, K, scaling, fault="no_selection_bias")
    assert rel_l2(unbiased, routed_want, mask) > 0.1

    routed_total, held_assignments = 0.0, 0.0
    for first in range(0, E, 2):
        share = dataclasses.replace(CFG, moe_experts_held=2, moe_first_expert=first)
        mine = {k: whole[k] for k in ("router", "router_bias", "shared_expert")}
        mine.update({k: whole[k][first : first + 2] for k in ("w_gate", "w_up", "w_down")})
        y, aux = MoEMLP(share).apply({"params": mine}, n, mask)
        routed_part, shared_part = reference.moe_layer(mine, n, K, scaling, first=first)
        assert rel_l2(y, routed_part + shared_part, mask) < TOL
        routed_total = routed_total + (y - shared_part)  # every chip computes the shared expert alike
        held_assignments += float(aux[6])
    assert rel_l2(routed_total + shared_want, routed_want + shared_want, mask) < TOL
    assert held_assignments == float(jnp.sum(mask)) * K
    y_all, _ = MoEMLP(CFG).apply({"params": whole}, n, mask)
    assert rel_l2(y_all, routed_want + shared_want, mask) < TOL


# the parameter trees of the benchmark's other expert configurations, as the
# parent commit built them: sha256 over the sorted "path shape dtype" lines.
# No router without `topk_method: noaux_tc` gains the selection bias, so their
# set-up programs and checkpoints stay what they were
TREES_BEFORE = {
    "olmoe-1b-7b-l2": "002ea6f4af025604bc9e7325f1fc01fb5dddc218c12e9441277e3e5147c6aa29",
    "smallthinker-21b-a3b-l4e16": "e8dc084292e4b95374d329ac4311d3d08fdf5439b3dcb12e8b3d430e1b12cff9",
    "pangu-ultra-moe-718b-l5e8": "d3278226a363b9366ba81386dfed71e0f7ccf84cc40a39e64cce4d22a7069403",
}


def tree_digest(config_name):
    from chipbench import job
    from trlx_tpu.data.configs import ModelConfig, ParallelConfig
    from trlx_tpu.models.builder import resolve_transformer_config

    file = job.load_config(config_name)
    tcfg, _ = resolve_transformer_config(ModelConfig(**file["job"]["model"]), ParallelConfig(**file["job"]["parallel"]))
    shapes = jax.eval_shape(lambda: CausalTransformer(tcfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    lines = sorted(f"{jax.tree_util.keystr(p)} {x.shape} {x.dtype}" for p, x in jax.tree_util.tree_leaves_with_path(shapes))
    return hashlib.sha256("\n".join(lines).encode()).hexdigest(), lines


@pytest.mark.parametrize("config_name", sorted(TREES_BEFORE))
def test_a_router_without_noaux_tc_keeps_its_parameter_tree(config_name):
    digest, lines = tree_digest(config_name)
    assert not [l for l in lines if "router_bias" in l or "indexer" in l]
    assert digest == TREES_BEFORE[config_name]


# ---------------------------------------------------------------------------
# the preset and the configuration file
# ---------------------------------------------------------------------------

PUBLISHED = {  # the catalog row's `config`, by TransformerConfig field
    "hidden_size": 6144, "intermediate_size": 12288, "moe_intermediate_size": 2048, "expert_width": 2048,
    "kv_lora_rank": 512, "q_lora_rank": 2048, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "dims_per_head": 256, "v_dims_per_head": 256, "num_heads": 64, "kv_heads": 64,
    "index_topk": 2048, "index_heads": 32, "index_head_dim": 128,
    "num_experts": 256, "num_experts_per_tok": 8, "num_shared_experts": 1, "first_k_dense": 3,
    "moe_renormalize": True, "routed_scaling_factor": 2.5, "sandwich_norm": False, "num_layers": 78,
    "layer_norm_epsilon": 1e-5, "rope_theta": 8000000.0, "max_position_embeddings": 1048576,
    "tie_word_embeddings": False, "vocab_size": 154880, "activation": "silu", "attn_bias": False,
    "model_type": "glm_moe_dsa", "moe_scoring": "sigmoid", "moe_topk_method": "noaux_tc",
}


@pytest.mark.parametrize("field", sorted(PUBLISHED))
def test_preset_holds_the_published_value(field):
    big = config_from_spec("builtin:glm-5.2")
    assert getattr(big, field) == PUBLISHED[field]
    assert hash(big) == hash(config_from_spec("builtin:glm-5.2"))


def test_the_cut_is_the_configuration_files_and_its_widths_check():
    import json

    from chipbench import job
    from trlx_tpu.data.configs import ModelConfig, ParallelConfig

    big = config_from_spec("builtin:glm-5.2")
    file = job.load_config("glm-5.2-l5e8")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        catalog = next(row for row in map(json.loads, f) if row["name"] == "GLM-5.2")
    assert list(big.indexer_types) == catalog["config"]["indexer_types"] and len(big.indexer_types) == 78
    assert [l.indexer for l in big.layer_layouts[2:7]] == list(TYPES) == file["published"]["indexer_types"]
    assert [l.ffn for l in big.layer_layouts[:5]] == ["dense"] * 3 + ["moe"] * 2
    # every number of the catalog's config under the same key, but the reduced ones
    for key, value in catalog["config"].items():
        if key not in file["reduced"]:
            assert file["published"][key] == value, key
    model = file["job"]["model"]
    cut = config_from_spec(model["model_path"], **model["model_extra_kwargs"])
    assert (cut.num_layers, cut.first_k_dense, cut.experts_held, cut.num_experts, cut.vocab_size) == (
        5, 1, 8, 256, 19360)
    assert [(l.ffn, l.indexer) for l in cut.layer_layouts] == [("dense", "full")] + [("moe", k) for k in TYPES[1:]]
    assert file["published"]["n_routed_experts"] == 8 and file["router_width"] == 256
    assert sorted(file["reduced"]) == sorted(next(
        c["reduced"] for c in job.load_benchmark()["configs"] if c["name"] == "glm-5.2-l5e8"))
    assert model["peft_kwargs"]["modified_modules"] == ["q_a_proj", "q_b_proj", "kv_a_proj", "o_proj"]
    cfg = types.SimpleNamespace(model=ModelConfig(**model), parallel=ParallelConfig(**file["job"]["parallel"]))
    job.check_published_widths(cfg, file)
    # 2673 M parameters at this cut (the configuration file's arithmetic), 18.7 M of them two indexers
    shapes = jax.eval_shape(lambda: CausalTransformer(cut).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert abs(n / 1e6 - 2673) < 2
    assert sorted(k for k in shapes if k.startswith("h_") and "indexer" in shapes[k]["attn"]) == ["h_0", "h_4"]


def test_collection_counters_name_the_index_cache():
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.trainer.ppo import PPOTrainer

    cfg = default_ppo_config().evolve(
        tokenizer=dict(tokenizer_path="builtin:bytes"), train=dict(tracker=None),
        model=dict(model_path="builtin:glm-test", num_layers_unfrozen=1),
        parallel=dict(param_dtype="float32", compute_dtype="float32"))
    trainer = PPOTrainer(cfg, reward_fn=lambda samples, **kw: [0.0] * len(samples))
    trainer._note_dense_kv_gauge((3, 21), GenerationConfig(max_new_tokens=19))
    assert trainer.last_cache_stats == {
        "rollout/kv_cache_bytes": 0.0, "rollout/ssm_state_bytes": 0.0, "rollout/kv_lane_heads": 1.0,
        "rollout/latent_cache_bytes": float(5 * 3 * 40 * (16 + 8) * 4),
        "rollout/index_cache_bytes": float(2 * 3 * 40 * 12 * 4),
        "rollout/sparse_gather_rows": 5.0 * 8}, trainer.last_cache_stats
    assert trainer.last_kv_layers == ((40, False),) * 5


# ---------------------------------------------------------------------------
# trlx_tpu.train(): the normal PPO path with adapters
# ---------------------------------------------------------------------------


def test_train_runs_ppo_with_adapters_under_a_selection_that_binds(tmp_path):
    """``trlx_tpu.train()`` with PPO, a value head, the hydra branch over the
    last block and LoRA on the four adaptable projections, rows of 32 slots
    under a selection of 8: the same trainer, collector, sampler, scoring
    forward and train step as every preset. Policy and branch start at KL 0;
    after two steps the last block's adapters and the value head have changed
    and nothing else has: not the indexer, not the selection bias."""
    import trlx_tpu.trlx as trlx
    from trlx_tpu.data.default_configs import default_ppo_config

    config = default_ppo_config().evolve(
        train=dict(seq_length=32, batch_size=4, total_steps=2, eval_interval=10,
                   checkpoint_interval=10, epochs=1, save_best=False, tracker=None,
                   checkpoint_dir=str(tmp_path / "ckpts"), logging_dir=str(tmp_path / "logs")),
        model=dict(model_path="builtin:glm-test", num_layers_unfrozen=1,
                   model_extra_kwargs=dict(moe_experts_held=2, moe_first_expert=2),
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
    prompts = ["".join(chr(97 + c) for c in rng.randint(0, 26, size=20)) for _ in range(8)]
    trainer = trlx.train(
        reward_fn=lambda samples, prompts, outputs, **kw: [float(i % 4) for i, _ in enumerate(outputs)],
        prompts=prompts, config=config, init_trainer_hook=hook)
    assert trainer.tcfg.model_type == "glm_moe_dsa" and trainer.tcfg.lora_r == 4 and trainer.tcfg.index_topk == 8
    collection = next(r for r in records if "time/exp" in r)
    assert float(collection.get("policy/sqrt_kl", collection.get("policy/sqrt_ref_kl"))) < 1e-6
    assert collection["rollout/kv_cache_bytes"] == 0.0
    S = int(collection["rollout/latent_cache_bytes"] // (5 * 8 * (16 + 8) * 4))
    assert 32 <= S <= 40 and collection["rollout/index_cache_bytes"] == 2 * 8 * S * 12 * 4
    assert collection["rollout/kv_read_frac"] == pytest.approx(8 / S)
    step = next(r for r in records if "time/train_step" in r)
    assert step["learn/attn_selected_frac"] == pytest.approx(selected_frac(int(step["learn/step_width"]), 8))
    assert 0.0 < float(step["moe/held_frac"]) < 0.7 and float(step["moe/dropped_frac"]) == 0.0
    assert np.isfinite([v for k, v in step.items() if k.startswith("losses/")]).all()
    changed = set()
    after = jax.tree_util.tree_map(np.asarray, trainer.state.params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(after), jax.tree_util.tree_leaves(before["params"])):
        if not np.array_equal(a, b):
            changed.add(jax.tree_util.keystr(path))
    assert changed and all("v_head" in k or ("['h_4']" in k and "lora_" in k) for k in changed), changed
    assert any("lora_b" in k for k in changed) and any("v_head" in k for k in changed)
    adapted = {jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(after)
               if "lora_" in jax.tree_util.keystr(path)}
    assert adapted and not any("kv_b_proj" in k or "indexer" in k for k in adapted)
