"""SmallThinker (window + rotary layers beside global NoPE layers, a router that
reads the block's input, ReGLU experts, a chip's share of the experts) against
the plain float32 reference the benchmark keeps,
``chipbench/reference/smallthinker.py``.

Toy size on the CPU (``builtin:smallthinker-test``: one period of four layers,
hidden 64, 4 heads / 2 KV heads of 16, 8 experts of 32 top-3, window 8),
float32 on both sides, so the mathematics has to agree: rows of 24 to 40
tokens, so a window layer's ring of 8 slots wraps more than once; left
padding; the sampler's prefill of a prompt longer than the window and its
single-token steps through the ring; the GRPO loss's gradients; the four
slices of two experts adding up to the uncut layer. Also here: what a mixed
layout does under ``scan_layers`` and on the whole-row rollout paths.
"""

import dataclasses
import hashlib
import json
import os
import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import smallthinker as reference
from trlx_tpu.models.transformer import (
    CausalTransformer,
    LayerLayout,
    MoEMLP,
    TransformerConfig,
    config_from_spec,
    make_kv_cache,
)
from trlx_tpu.ops import sampling
from trlx_tpu.ops.cache_layout import refuse
from trlx_tpu.ops.sampling import GenerationConfig, generate, kv_slots_read, layer_extents

# Relative L2 of the logits (or of a gradient leaf). Both sides compute in
# float32 and the CPU's matmuls are exact float32, so what is left is the
# order of summation: grouped matmuls over rows sorted by expert against one
# dense pass an expert, a ring read in ring order against keys in slot order.
# Measured 3e-6 to 6e-6 on the logits of nine seeds (q and k four times the
# other matrices: sharp softmaxes); the mildest planted fault reads 2e-2.
TOL = 1e-4

CFG = TransformerConfig.smallthinker("test", param_dtype=jnp.float32, dtype=jnp.float32,
                                     attention_impl="xla")
# one chip's share: experts 2 and 3 of the router's 8
HELD = dataclasses.replace(CFG, moe_experts_held=2, moe_first_expert=2)
WINDOW = CFG.sliding_window
B, T = 3, 40


def dims_of(cfg):
    return {
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.kv_heads,
        "head_dim": cfg.dims_per_head,
        "rms_norm_eps": cfg.layer_norm_epsilon,
        "rope_theta": cfg.rope_theta,
        "moe_num_active_primary_experts": cfg.num_experts_per_tok,
        "moe_num_primary_experts": cfg.experts_held,
        "moe_first_expert_held": cfg.moe_first_expert,
        "sliding_window_size": cfg.sliding_window,
        "sliding_window_layout": list(cfg.sliding_window_layout),
        "rope_layout": list(cfg.rope_layout),
    }


def seeded_params(seed, cfg=CFG):
    """The module's own tree, refilled: matrices at 1/sqrt(fan_in), q and k
    four times that (a softmax over 0.02-scale q.k is flat, and a window or a
    rotary embedding then moves nothing), norm scales scattered about 1."""
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
        else:  # [in, out] kernels and [E, in, out] expert stacks
            x = rs.randn(*leaf.shape) / np.sqrt(leaf.shape[-2])
            if names[-2] in ("q_proj", "k_proj"):
                x = 4.0 * x
        out.append(jnp.asarray(x, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def batch(seed, rows=B, width=T):
    """Left-padded rows: row ``i`` has ``8 * i`` padding tokens, so the real
    lengths are 40, 32 and 24."""
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


@pytest.mark.parametrize("cfg", [CFG, HELD], ids=["all_experts", "experts_2_and_3"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_matches_reference(seed, cfg):
    params, (ids, mask) = seeded_params(seed, cfg), batch(seed)
    out = CausalTransformer(cfg).apply({"params": params}, ids, attention_mask=mask)
    want = reference.logits(params, dims_of(cfg), ids, mask, (0, T))
    assert rel_l2(out["logits"], want, mask) < TOL
    load = np.asarray(out["router_load"])
    assert load[0] == 0.0  # nothing dropped: the layer has no capacity
    if cfg is HELD:
        # [.., held_frac, held_load_max_over_mean, compact_frac]: two of eight experts held
        # (a quarter of the experts held: no bound on the row buffers, no call counted)
        assert load.shape == (5,) and 0.05 < load[2] < 0.6 and 1.0 <= load[3] <= 2.0 and load[4] == 0.0
    else:
        assert load.shape == (2,)


def test_left_padded_row_is_the_row_alone():
    params, (ids, mask) = seeded_params(3), batch(3)
    alone = system_logits(params, ids[2:, 16:], mask[2:, 16:])
    assert rel_l2(system_logits(params, ids, mask)[2:, 16:], alone, mask[2:, 16:]) < 1e-5


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_planted_fault_moves_the_logits(fault):
    params, (ids, mask) = seeded_params(5), batch(5)
    dims = dims_of(CFG)
    clean = reference.logits(params, dims, ids, mask, (0, T))
    moved = rel_l2(reference.logits(params, dims, ids, mask, (0, T), fault=fault), clean, mask)
    assert moved > 100 * TOL, (fault, moved)
    assert rel_l2(system_logits(params, ids, mask), clean, mask) < TOL


def test_hydra_branch_replays_both_kinds_of_layer():
    """The top three blocks are a window layer, a window layer and... the
    branch from block 1 holds window layers only, from block 0 the global one
    too: each replay is the full forward's top."""
    params, (ids, mask) = seeded_params(4), batch(4)
    model = CausalTransformer(CFG)
    for branch_layer in (2, 4):
        full = model.apply({"params": params}, ids, attention_mask=mask, branch_layer=branch_layer)
        top = model.apply({"params": params}, full["branch_input"], branch_layer, mask,
                          method=CausalTransformer.forward_branch)
        assert rel_l2(top["logits"], full["logits"], mask) < 1e-6


# ---------------------------------------------------------------------------
# the sampler's cache: a ring for the window layers
# ---------------------------------------------------------------------------


def test_cache_tree_holds_a_ring_for_each_window_layer():
    shapes = [layer["k"].shape for layer in jax.eval_shape(lambda: make_kv_cache(CFG, B, T))]
    kv, d = CFG.kv_heads, CFG.dims_per_head
    assert shapes == [(B, T, kv, d)] + [(B, WINDOW, kv, d)] * 3
    short = [layer["k"].shape[1] for layer in jax.eval_shape(lambda: make_kv_cache(CFG, B, 5))]
    assert short == [5, 5, 5, 5]  # a row inside the window: every layer holds the row
    assert CFG.layer_layouts == (LayerLayout(None, False, "moe"),) + (LayerLayout(WINDOW, True, "moe"),) * 3
    uniform = TransformerConfig.mistral("test")
    assert uniform.layer_layouts == (LayerLayout(8, True),) * 2 and not uniform.mixed_layout


@pytest.mark.parametrize("prompt", [5, 13, 21, 32])
def test_prefill_then_decode_through_the_ring_matches_reference_full_forward(prompt):
    """A prompt shorter than the window (no wrap yet), longer (its last 8
    positions stay), and 21 and 32 (the ring is overwritten more than once
    before decoding starts); then one token at a time, slot ``t`` written at
    ``t mod 8``, to 40: logits, not tokens, at every position."""
    params, (ids, mask) = seeded_params(6), batch(6)
    model = CausalTransformer(CFG)
    want = reference.logits(params, dims_of(CFG), ids, mask, (0, T))
    slots = jnp.concatenate([mask[:, :prompt], jnp.zeros((B, T - prompt), jnp.int32)], axis=1)
    step = jax.jit(lambda ids_, slots_, cache_, at: model.apply(
        {"params": params}, ids_, attention_mask=slots_, cache=cache_, cache_index=at))
    out = model.apply({"params": params}, ids[:, :prompt], attention_mask=slots,
                      cache=make_kv_cache(CFG, B, T), cache_index=jnp.asarray(0, jnp.int32))
    assert rel_l2(out["logits"], want[:, :prompt], mask[:, :prompt]) < TOL
    for t in range(prompt, T):
        slots = slots.at[:, t].set(mask[:, t])
        out = step(ids[:, t : t + 1], slots, out["cache"], jnp.asarray(t))
        assert [layer["k"].shape[1] for layer in out["cache"]] == [T, WINDOW, WINDOW, WINDOW]
        assert rel_l2(out["logits"], want[:, t : t + 1], mask[:, t : t + 1]) < TOL, t


def test_generate_records_the_references_logprobs(monkeypatch):
    """``generate()`` itself, sampling at temperature 1 from a 21-token
    left-padded prompt for 19 steps with a bucket of 4 slots: the global
    layer reads extents of 24, 28, ..., 40 slots, the window layers their
    ring, and the logprob the sampler recorded for each token is the
    reference's on the finished row."""
    monkeypatch.setattr(sampling, "KV_BUCKET", 4)
    params, (ids, mask) = seeded_params(8), batch(8)
    P, N = 21, T - 21
    model = CausalTransformer(CFG)
    apply_fn = lambda p, i, **kw: model.apply({"params": p}, i, **kw)
    seen = []

    def noting(p, i, **kw):
        seen.append(kw.get("kv_extents"))
        return apply_fn(p, i, **kw)

    config = GenerationConfig(max_new_tokens=N, eos_token_id=None, pad_token_id=0)
    out = jax.jit(lambda r: generate(noting, params, lambda b, s: make_kv_cache(CFG, b, s),
                                     ids[:, :P], mask[:, :P], r, config))(jax.random.PRNGKey(1))
    assert seen[-1] == (24, 28, 32, 36, 40)
    full_mask = jnp.concatenate([mask[:, :P], out.response_mask], axis=1)
    want = reference.logits(params, dims_of(CFG), out.sequences, full_mask, (P - 1, T - 1))
    want_lp = jnp.take_along_axis(jax.nn.log_softmax(want), out.response_tokens[..., None], axis=-1)[..., 0]
    assert float(jnp.max(jnp.abs(want_lp - out.response_logprobs))) < 1e-4


def test_whole_row_paths_run_a_mixed_layout_over_full_length_caches():
    """Speculative verify and slot refill hand the model a ``[B]`` vector of
    cache indices over caches as long as the row. Each layer's bias carries
    its own window and each layer its own rotary, so the step is right; it is
    the ring they cannot write (next test)."""
    params, (ids, mask) = seeded_params(9), batch(9)
    model = CausalTransformer(CFG)
    want = reference.logits(params, dims_of(CFG), ids, mask, (0, T))
    unbounded = dataclasses.replace(CFG, sliding_window=None, sliding_window_layout=None)
    cache = make_kv_cache(unbounded, B, T)  # every layer T slots
    slots = jnp.concatenate([mask[:, :30], jnp.zeros((B, T - 30), jnp.int32)], axis=1)
    out = model.apply({"params": params}, ids[:, :30], attention_mask=slots, cache=cache,
                      cache_index=jnp.asarray(0, jnp.int32))
    slots = slots.at[:, 30:33].set(1)
    out = model.apply({"params": params}, ids[:, 30:33], attention_mask=slots, cache=out["cache"],
                      cache_index=jnp.full((B,), 30, jnp.int32))
    assert rel_l2(out["logits"], want[:, 30:33], mask[:, 30:33]) < TOL


RING_REFUSAL = (r"{path} does not support a model whose cache holds per-head K and V in a ring of 8 slots for a row of {slots} "
                r"\(leaves \['k', 'v'\]\): .*B3c\); use the plain sampler")


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


@pytest.mark.parametrize("build,path,slots", [
    (lambda: build_slot_refill(paged=False), "slot_refill", 12),
    (lambda: build_slot_refill(paged=True), "engine", 12),
    (build_prefix_cache, "prefix_cache", 16),
], ids=["slot_refill", "engine", "prefix_cache"])
def test_whole_row_path_refuses_a_ring_by_name(build, path, slots):
    with pytest.raises(NotImplementedError, match="^" + RING_REFUSAL.format(path=path, slots=slots)):
        build()
    refuse(jax.eval_shape(lambda: make_kv_cache(CFG, 2, 8)), path, 8)  # inside the window: passes


def test_speculation_with_a_separate_draft_is_refused_by_the_ring_it_would_overrun():
    """Speculation's verify writes a span of gamma + 1 tokens at each row's own
    index, which a ring takes where it holds window + gamma slots: a model
    that drafts with its own module gets those (tests/test_exaone_moe.py),
    this one's rings are the window's 8, and the model says so by name."""
    from trlx_tpu.ops.speculative import generate_speculative

    draft_cfg = TransformerConfig.gpt2("test", param_dtype=jnp.float32, dtype=jnp.float32)
    draft_cfg = dataclasses.replace(draft_cfg, vocab_size=CFG.vocab_size)
    target, draft = CausalTransformer(CFG), CausalTransformer(draft_cfg)
    ids = jnp.ones((2, 8), jnp.int32)
    d_params = draft.init(jax.random.PRNGKey(0), ids)["params"]
    with pytest.raises(NotImplementedError, match="a span of 5 tokens at each row's own index into a ring cache of 8 slots"):
        generate_speculative(
            lambda p, i, **kw: target.apply({"params": p}, i, **kw), seeded_params(0),
            lambda p, i, **kw: draft.apply({"params": p}, i, **kw), d_params,
            cache_of(CFG), cache_of(draft_cfg), ids, ids, jax.random.PRNGKey(0), GenerationConfig(max_new_tokens=4))


RING_PROGRAMS = os.path.join(os.path.dirname(__file__), "fixtures", "smallthinker_ring_programs_before_exaone.json")


def ring_program_fingerprints():
    """sha256 of the toy's cache tree for rows of 16 slots (rings of 8) and of
    the jaxpr text of the sampler's two programs on it: the prefill of 12
    tokens from slot 0 and the single-token step at slot 12."""
    cfg = config_from_spec("builtin:smallthinker-test", attention_impl="xla")
    model = CausalTransformer(cfg)
    ids = jnp.zeros((2, 12), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids)["params"])
    cache = jax.eval_shape(lambda: make_kv_cache(cfg, 2, 16))
    slots = jnp.ones((2, 16), jnp.int32)
    texts = {
        "cache": str(jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), cache)),
        "prefill": str(jax.make_jaxpr(
            lambda p, c: model.apply({"params": p}, ids, attention_mask=slots, cache=c,
                                     cache_index=jnp.asarray(0, jnp.int32)))(params, cache)),
        "decode": str(jax.make_jaxpr(
            lambda p, c: model.apply({"params": p}, ids[:, :1], attention_mask=slots, cache=c,
                                     cache_index=jnp.asarray(12, jnp.int32), kv_extents=(8, 16)))(params, cache)),
    }
    clean = lambda text: re.sub(r"0x[0-9a-f]+", "0x", text)
    return {k: hashlib.sha256(clean(v).encode()).hexdigest() for k, v in texts.items()}


@pytest.mark.parametrize("program", ["cache", "prefill", "decode"])
def test_ring_programs_are_the_ones_recorded_before_the_per_row_ring(program, clean_trace_state):
    """Recorded on PR 46's parent by this function: ``make_kv_cache`` and
    ``_ring_plan`` took a ``[B]`` vector of cache indices, a span past slot 0
    and ``gamma`` more slots for a model that drafts, under this model's feet,
    and its cache tree and both programs through the ring are byte for byte
    what they were (the benchmark's cell 6)."""
    with jax.default_matmul_precision(None), open(RING_PROGRAMS) as f:
        assert ring_program_fingerprints()[program] == json.load(f)[program]


@pytest.mark.parametrize("how", ["vector_cache_index", "span_past_slot_zero"])
def test_model_refuses_what_it_cannot_write_into_a_ring(how):
    params, (ids, mask) = seeded_params(2), batch(2)
    cache = make_kv_cache(CFG, B, T)
    at = {"vector_cache_index": jnp.full((B,), 12, jnp.int32), "span_past_slot_zero": 12}[how]
    with pytest.raises(NotImplementedError, match="ring cache"):
        CausalTransformer(CFG).apply({"params": params}, ids[:, 12:14], attention_mask=mask,
                                     cache=cache, cache_index=at)


def test_scan_layers_refuses_a_mixed_layout_by_name():
    scanned = dataclasses.replace(CFG, scan_layers=True)
    with pytest.raises(NotImplementedError, match="scan_layers.*'smallthinker'.*more than one attention layout.*B3"):
        CausalTransformer(scanned).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


# ---------------------------------------------------------------------------
# the learner
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [CFG, HELD], ids=["all_experts", "experts_2_and_3"])
def test_grpo_loss_gradients_match_the_references(cfg):
    """The GRPO objective on a 17-token query (its last token real in every
    row, as a left-padded query's is) and a 23-token response, through the
    global layer, the window layers (the window binds from position 8 on) and
    the expert layer, against autodiff through the reference."""
    from trlx_tpu.data.default_configs import default_grpo_config
    from trlx_tpu.utils.stats import logprobs_of_labels

    method = default_grpo_config().method
    params, (ids, mask) = seeded_params(7, cfg), batch(7)
    Q = 17
    rs = np.random.RandomState(7)
    old = jnp.asarray(-3.0 + 0.3 * rs.randn(B, T - Q), jnp.float32)
    ref = jnp.asarray(-3.0 + 0.3 * rs.randn(B, T - Q), jnp.float32)
    adv = jnp.asarray([1.0, -0.5, 0.25], jnp.float32)

    def loss(logits_of):
        def f(p):
            lp = logprobs_of_labels(logits_of(p), ids[:, Q:])
            # old logprobs a fixed distance from the current ones, so that some
            # ratios are clipped and some are not
            return method.loss(logprobs=lp, old_logprobs=jax.lax.stop_gradient(lp) + 0.3 * (old + 3.0),
                               ref_logprobs=ref, advantages=adv, mask=mask[:, Q:])[0]
        return f

    model = CausalTransformer(cfg)
    got = jax.grad(loss(lambda p: model.apply(
        {"params": p}, ids, attention_mask=mask, logits_span=(Q - 1, T - 1))["logits"]))(params)
    want = jax.grad(loss(lambda p: reference.logits(p, dims_of(cfg), ids, mask, (Q - 1, T - 1))))(params)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        err = float(jnp.linalg.norm(g - w) / jnp.maximum(jnp.linalg.norm(w), 1e-12))
        assert err < TOL, (jax.tree_util.keystr(path), err)


# ---------------------------------------------------------------------------
# one chip's share of the experts
# ---------------------------------------------------------------------------


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips hold experts 0-1, 2-3, 4-5, 6-7 of one layer and the same
    router. Each routes over all eight, renormalises over all three chosen
    and computes its own experts' part; the parts sum to what the uncut
    reference gives for the whole layer, and every real assignment is
    computed by exactly one of them."""
    rs = np.random.RandomState(11)
    d, f, E = CFG.hidden_size, CFG.intermediate_size, CFG.num_experts
    whole = {
        "router": {"kernel": jnp.asarray(rs.randn(d, E), jnp.float32)},
        **{name: jnp.asarray(rs.randn(*shape) / np.sqrt(shape[-2]), jnp.float32)
           for name, shape in (("w_gate", (E, d, f)), ("w_up", (E, d, f)), ("w_down", (E, f, d)))},
    }
    n = jnp.asarray(rs.randn(B, T, d), jnp.float32)
    router_input = jnp.asarray(rs.randn(B, T, d), jnp.float32)
    _, mask = batch(0)
    want = reference.moe_layer(whole, n, router_input, CFG.num_experts_per_tok)

    total, held_assignments = 0.0, 0.0
    for first in range(0, E, 2):
        share = dataclasses.replace(CFG, moe_experts_held=2, moe_first_expert=first)
        mine = {"router": whole["router"],
                **{k: whole[k][first : first + 2] for k in ("w_gate", "w_up", "w_down")}}
        y, aux = MoEMLP(share).apply({"params": mine}, n, mask, router_input)
        part = reference.moe_layer(mine, n, router_input, CFG.num_experts_per_tok, first=first)
        assert rel_l2(y, part, mask) < TOL
        total = total + y
        held_assignments += float(aux[6])
        assert float(aux[3]) == 0.0
    assert rel_l2(total, want, mask) < TOL
    assert held_assignments == float(jnp.sum(mask)) * CFG.num_experts_per_tok
    y_all, aux_all = MoEMLP(CFG).apply({"params": whole}, n, mask, router_input)
    assert rel_l2(y_all, want, mask) < TOL and aux_all.shape == (6,)


def test_held_experts_need_dropless_routing():
    with pytest.raises(NotImplementedError, match="moe_experts_held.*dropless"):
        MoEMLP(dataclasses.replace(HELD, moe_capacity_factor=1.25)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, CFG.hidden_size)))


@pytest.mark.parametrize("activation", ["relu", "silu", "gelu"])
def test_the_gate_is_a_flag_and_not_the_activations_name(activation):
    x = jnp.zeros((1, 4, CFG.hidden_size))
    gated = MoEMLP(dataclasses.replace(CFG, activation=activation)).init(jax.random.PRNGKey(0), x)
    plain = MoEMLP(dataclasses.replace(CFG, activation=activation, moe_gated=False)).init(jax.random.PRNGKey(0), x)
    assert "w_gate" in gated["params"] and "w_gate" not in plain["params"]
    for family in ("mixtral", "olmoe"):
        assert config_from_spec(f"builtin:{family}-test").moe_gated


# ---------------------------------------------------------------------------
# the preset, the counters, the converter
# ---------------------------------------------------------------------------

PUBLISHED = {  # the catalog row's `config`, by TransformerConfig field
    "dims_per_head": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "intermediate_size": 768, "num_experts_per_tok": 6, "num_experts": 64,
    "moe_renormalize": True, "num_heads": 28, "num_layers": 52, "kv_heads": 4,
    "layer_norm_epsilon": 1e-6, "rope_theta": 1500000.0, "sliding_window": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936,
    "rope_layout": (0, 1, 1, 1) * 13, "sliding_window_layout": (0, 1, 1, 1) * 13,
}


@pytest.mark.parametrize("field", sorted(PUBLISHED))
def test_preset_holds_the_published_value(field):
    big = config_from_spec("builtin:smallthinker-21b-a3b")
    assert getattr(big, field) == PUBLISHED[field]
    assert hash(big) == hash(config_from_spec("builtin:smallthinker-21b-a3b"))


def test_preset_is_the_families_block_and_the_cut_is_the_configuration_files():
    from chipbench import job

    big = config_from_spec("builtin:smallthinker-21b-a3b")
    assert (big.norm, big.activation, big.moe_gated, big.moe_router_input, big.position_scheme) == (
        "rmsnorm", "relu", True, "block_input", "rotary")
    assert (big.attn_bias, big.mlp_bias, big.qk_norm, big.moe_capacity_factor) == (False, False, False, 0.0)
    assert big.layer_layouts[:5] == (LayerLayout(None, False, "moe"),) + (LayerLayout(4096, True, "moe"),) * 3 + (LayerLayout(None, False, "moe"),)
    file = job.load_config("smallthinker-21b-a3b-l4e16")
    cut = config_from_spec(file["job"]["model"]["model_path"], **file["job"]["model"]["model_extra_kwargs"])
    assert (cut.num_layers, cut.experts_held, cut.moe_first_expert, cut.num_experts, cut.vocab_size) == (
        4, 16, 0, 64, 37984)
    published = file["published"]
    assert list(cut.rope_layout[:4]) == published["rope_layout"] == [0, 1, 1, 1]
    assert list(cut.sliding_window_layout[:4]) == published["sliding_window_layout"] == [0, 1, 1, 1]
    assert published["moe_num_primary_experts"] == 16 and file["router_width"] == 64
    assert [layer["k"].shape[1] for layer in jax.eval_shape(lambda: make_kv_cache(cut, 16, 8192))] == [
        8192, 4096, 4096, 4096]


@pytest.mark.parametrize("extents,slots,want", [
    ((24, 28, 32, 36, 40), 40, (24, 28, 32, 36, 40)),  # the global layer: the row's own
    ((24, 28, 32, 36, 40), 8, (8,)),                   # a ring shorter than the prompt
    ((8, 12, 16), 12, (8, 12)),                        # a ring the row grows into
    ((40,), 8, (8,)),
])
def test_a_layers_extents_are_the_rows_cut_to_its_cache(extents, slots, want):
    assert layer_extents(extents, slots) == want


def test_ring_reads_its_slots_at_every_step():
    assert kv_slots_read((8,), 21, 19) == 19 * 8
    assert kv_slots_read((8, 12), 6, 10) == 2 * 8 + 8 * 12  # slots 7, 8 written under extent 8


def test_collection_counters_split_the_cache_by_layer_kind():
    from trlx_tpu.data.default_configs import default_grpo_config
    from trlx_tpu.trainer.grpo import GRPOTrainer

    cfg = default_grpo_config().evolve(
        tokenizer=dict(tokenizer_path="builtin:bytes"), train=dict(tracker=None),
        model=dict(model_path="builtin:smallthinker-test"),
        parallel=dict(param_dtype="float32", compute_dtype="float32"))
    trainer = GRPOTrainer(cfg, reward_fn=lambda samples, **kw: [0.0] * len(samples))
    trainer._note_dense_kv_gauge((3, 21), GenerationConfig(max_new_tokens=19))
    row = 2 * 3 * trainer.tcfg.kv_heads * trainer.tcfg.dims_per_head * 4  # k and v, 3 rows, float32, a slot
    assert trainer.last_cache_stats == {
        "rollout/kv_cache_bytes": float(row * (40 + 3 * 8)), "rollout/ssm_state_bytes": 0.0, "rollout/kv_lane_heads": 1.0,
        "rollout/kv_cache_window_bytes": float(row * 3 * 8),
        "rollout/kv_cache_global_bytes": float(row * 40)}
    assert trainer.last_kv_layers == ((40, False), (8, True), (8, True), (8, True))


def test_hf_interop_says_there_is_no_converter():
    from trlx_tpu.models.hf_interop import config_from_hf

    with pytest.raises(ValueError, match="smallthinker.*no HF checkpoint conversion"):
        config_from_hf(types.SimpleNamespace(model_type="smallthinker"))


# ---------------------------------------------------------------------------
# trlx_tpu.train(): the normal GRPO path
# ---------------------------------------------------------------------------


def test_train_runs_grpo_on_the_preset_and_logs_its_counters(tmp_path, monkeypatch):
    """``trlx_tpu.train()`` on ``builtin:smallthinker-test`` holding experts 2
    and 3: the same trainer, collector, sampler, scoring forward, hydra branch
    and train step as every other preset. Prompts of 20 letters and 12 new
    tokens: 32 slots, four times the window, so the sampler's window layers
    run rings; the records carry the layer kinds' cache bytes, the window
    layers' read share, the held experts' share and the blocks flash visits.
    A quarter of the experts held and calls of a few hundred rows have no
    bound on their row buffers (``held_row_bound``'s two floors): lifted
    here, so that the job runs the compact dispatch and logs its counter."""
    import trlx_tpu.trlx as trlx
    from trlx_tpu.models import transformer

    monkeypatch.setattr(transformer, "MOE_HELD_MIN_ROWS", 0)
    monkeypatch.setattr(transformer, "MOE_HELD_MIN_CUT", 1)
    from trlx_tpu.data.default_configs import default_grpo_config

    config = default_grpo_config().evolve(
        train=dict(seq_length=32, batch_size=4, total_steps=2, eval_interval=10,
                   checkpoint_interval=10, epochs=1, save_best=False, tracker=None,
                   checkpoint_dir=str(tmp_path / "ckpts"), logging_dir=str(tmp_path / "logs")),
        model=dict(model_path="builtin:smallthinker-test", num_layers_unfrozen=2,
                   model_extra_kwargs=dict(moe_experts_held=2, moe_first_expert=2)),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        method=dict(num_rollouts=8, chunk_size=8, group_size=4, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=12, min_new_tokens=12, top_k=0, top_p=1.0, do_sample=True)),
    )
    records = []

    def hook(trainer):
        trainer.tracker = types.SimpleNamespace(
            log=lambda stats, step=None: records.append(dict(stats)), finish=lambda: None)

    rng = np.random.RandomState(0)
    prompts = ["".join(chr(97 + c) for c in rng.randint(0, 26, size=20)) for _ in range(2)]
    trainer = trlx.train(
        reward_fn=lambda samples, prompts, outputs, **kw: [float(i) for i, _ in enumerate(outputs)],
        prompts=prompts, config=config, init_trainer_hook=hook)
    assert trainer.tcfg.model_type == "smallthinker" and trainer.tcfg.experts_held == 2
    collection = next(r for r in records if "time/exp" in r)
    row = 2 * 8 * trainer.tcfg.kv_heads * trainer.tcfg.dims_per_head * trainer.tcfg.dtype.dtype.itemsize
    S = int(collection["rollout/kv_cache_global_bytes"] // row)  # the prompt's padded width + 12
    assert 32 <= S <= 40 and collection["rollout/kv_cache_global_bytes"] == row * S
    assert collection["rollout/kv_cache_window_bytes"] == row * 3 * 8
    assert collection["rollout/kv_cache_bytes"] == row * (S + 3 * 8)
    # twelve steps: the global layer reads all S slots (one extent), a ring its 8
    assert collection["rollout/kv_window_read_frac"] == 8 / S
    assert collection["rollout/kv_read_frac"] == (S + 3 * 8) / (4 * S)
    step = next(r for r in records if "time/train_step" in r)
    assert 0.0 < float(step["moe/held_frac"]) < 0.7 and float(step["moe/dropped_frac"]) == 0.0
    assert 1.0 <= float(step["moe/held_load_max_over_mean"]) <= 2.0
    # two of eight experts held, and the bound's two floors lifted above: the sorted row buffers have half of tokens x K
    # rows, up to a 128-row tile, and every call of the train step's forward fitted them
    assert float(step["moe/compact_frac"]) == 1.0
    assert step["learn/step_width"] <= 128 and step["learn/attn_visited_frac"] == 1.0  # one 128-slot block
    assert step["learn/attn_tile"] == 128.0 and step["learn/attn_interior_frac"] == 0.0  # and it holds the diagonal
    assert np.isfinite([v for k, v in step.items() if k.startswith("losses/")]).all()
