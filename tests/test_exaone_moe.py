"""K-EXAONE (GQA with a per-head QK-norm, a window of 128 on three layers in
four with rotary there and none on the global layer, a leading dense layer, a
shared expert beside sigmoid-routed ones, a chip's share of the experts, and a
next-token-prediction module with which the model drafts its own rollouts)
against the plain float32 reference the benchmark keeps,
``chipbench/reference/exaone_moe.py``.

Toy size on the CPU (``builtin:k-exaone-test``: 1 dense + 4 sparse layers,
four of them behind a window of 8 and the last global, hidden 64, 4 query and
2 key/value heads of 16, 8 experts of 32 top-2 and a shared one, one module),
float32 on both sides, so the mathematics has to agree: the full forward; the
sampler's prefill and single-token steps through rings shorter than the row;
the two-token verify at each row's own index through those rings; the module's
logits; loss and gradients of a PPO step's objective; every planted fault
caught or named as not caught; the four shares adding up with the shared
expert counted once; PPO with LoRA through ``trlx_tpu.train()``.
"""

import dataclasses
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import exaone_moe as reference
from trlx_tpu.models.transformer import (
    CausalTransformer,
    LayerLayout,
    MoEMLP,
    TransformerConfig,
    config_from_spec,
    make_kv_cache,
)
from trlx_tpu.ops.sampling import GenerationConfig, generate
from trlx_tpu.ops.speculative import generate_speculative, module_drafter

TOL = 1e-4  # relative L2 of float32 logits: what is left is the order of summation

CFG = TransformerConfig.exaone("test", param_dtype=jnp.float32, dtype=jnp.float32, attention_impl="xla")
# one chip's share: experts 2 and 3 of the router's 8
HELD = dataclasses.replace(CFG, moe_experts_held=2, moe_first_expert=2)
LORA = dataclasses.replace(CFG, lora_r=4, lora_alpha=8.0, lora_targets=("q_proj", "k_proj", "v_proj", "o_proj"))
B, T = 3, 40
L = CFG.num_layers


def dims_of(cfg):
    kinds = cfg.layer_layouts
    return {
        "num_hidden_layers": cfg.num_layers,
        "layer_types": ["sliding_attention" if k.window else "full_attention" for k in kinds],
        "sliding_windows": [k.window or 0 for k in kinds],
        "first_k_dense_replace": cfg.first_k_dense,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.kv_heads,
        "head_dim": cfg.head_dim,
        "rms_norm_eps": cfg.layer_norm_epsilon,
        "rope_parameters": {"rope_theta": cfg.rope_theta, "rope_type": "default"},
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "num_experts": cfg.experts_held,
        "moe_first_expert_held": cfg.moe_first_expert,
        "lora_alpha": cfg.lora_alpha,
    }


def seeded_params(seed, cfg=CFG):
    """The module's own tree, refilled: matrices at 1/sqrt(fan_in), norm
    scales scattered about 1 (the per-head q and k scales about 2: a flat
    softmax hides a fault of the scores), adapters' B not zero."""
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
            x = (2.0 if names[-2] in ("q_norm", "k_norm") else 1.0) + 0.2 * rs.randn(*leaf.shape)
        elif names[-1] == "embedding":
            x = rs.randn(*leaf.shape)
        else:  # [in, out] kernels, adapters and [E, in, out] expert stacks
            x = rs.randn(*leaf.shape) / np.sqrt(leaf.shape[-2])
            if names[-1] == "lora_b":  # a trained adapter: a tenth of its matrix
                x = 0.1 * x
        out.append(jnp.asarray(x, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def batch(seed, rows=B, width=T):
    """Left-padded rows: row ``i`` has ``5 * i`` padding tokens."""
    rs = np.random.RandomState(seed)
    ids = rs.randint(3, CFG.vocab_size - 3, (rows, width))
    mask = np.ones((rows, width), np.int32)
    for i in range(rows):
        mask[i, : 5 * i] = 0
    return jnp.asarray(ids, jnp.int32), jnp.asarray(mask)


def rel_l2(got, want, mask):
    m = np.asarray(mask, np.float64)[..., None]
    got, want = np.asarray(got, np.float64) * m, np.asarray(want, np.float64) * m
    return float(np.sqrt(((got - want) ** 2).sum() / (want**2).sum()))


def system_logits(params, ids, mask, cfg=CFG):
    return CausalTransformer(cfg).apply({"params": params}, ids, attention_mask=mask)["logits"]


def system_mtp_logits(params, ids, mask, cfg=CFG):
    """The module over a whole row: position t from the stack's hidden state
    at t and token t + 1."""
    model = CausalTransformer(cfg)
    hidden = model.apply({"params": params}, ids, attention_mask=mask)["pre_norm_hidden"]
    return model.apply({"params": params}, hidden[:, :-1], ids[:, 1:], attention_mask=mask[:, :-1],
                       method="draft")["logits"]


# ---------------------------------------------------------------------------
# the forward pass and the module
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [CFG, HELD, LORA], ids=["all_experts", "experts_2_and_3", "adapters"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_and_module_match_reference(seed, cfg):
    params, (ids, mask) = seeded_params(seed, cfg), batch(seed)
    dims = dims_of(cfg)
    assert rel_l2(system_logits(params, ids, mask, cfg), reference.logits(params, dims, ids, mask, (0, T)), mask) < TOL
    want = reference.mtp_logits(params, dims, ids, mask, (0, T - 1))
    assert rel_l2(system_mtp_logits(params, ids, mask, cfg), want, mask[:, :-1]) < TOL


def test_layouts_cache_tree_and_parameter_tree():
    sliding, full = LayerLayout(8, True, "moe"), LayerLayout(None, False, "moe")
    assert CFG.layer_layouts == (LayerLayout(8, True, "dense"),) + (sliding,) * 3 + (full,)
    assert CFG.layer_layout(L) == full  # the module's block: a global layer, no rotary
    cache = jax.eval_shape(lambda: make_kv_cache(CFG, B, T))
    # four rings of window + 1 slots (a drafting model verifies two tokens a round), the
    # global layer and the module's layer whole
    assert [layer["k"].shape[1] for layer in cache] == [9] * 4 + [T, T]
    plain = dataclasses.replace(CFG, mtp_layers=0)
    assert [layer["k"].shape[1] for layer in jax.eval_shape(lambda: make_kv_cache(plain, B, T))] == [8] * 4 + [T]
    params = seeded_params(0, LORA)
    assert sorted(params["mtp_0"]) == ["block", "e_norm", "eh_proj", "h_norm", "ln_f"]
    assert params["mtp_0"]["eh_proj"]["kernel"].shape == (128, 64)
    assert params["h_1"]["attn"]["q_norm"]["scale"].shape == (16,)  # one head's dims
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(params)]
    assert any("['h_4']" in p and "lora_a" in p for p in paths)
    assert not any("['mtp_0']" in p and "lora_" in p for p in paths)  # the module takes no adapter
    assert "mtp_0" not in seeded_params(0, plain)


def test_trainable_mask_freezes_the_module():
    from trlx_tpu.models.builder import grad_param_frac, is_frozen, trainable_mask

    params = {"backbone": seeded_params(0, LORA), "v_head": {"w": jnp.zeros((4,))}}
    for cfg, unfrozen in ((LORA, 1), (CFG, 2), (CFG, -1)):
        mask = trainable_mask(params, cfg, unfrozen)
        assert all(is_frozen(m) for m in jax.tree_util.tree_leaves(mask["backbone"]["mtp_0"]))
        assert any(not is_frozen(m) for m in jax.tree_util.tree_leaves(mask["backbone"]["h_4"]))
    assert grad_param_frac(params, trainable_mask(params, LORA, 1)) < 0.01


@pytest.mark.parametrize("fault", reference.FAULTS + (reference.PRECISION_CONTROL,))
def test_planted_fault_moves_the_logits(fault):
    params, (ids, mask) = seeded_params(5), batch(5)
    dims = dims_of(CFG)
    clean = reference.logits(params, dims, ids, mask, (0, T))
    moved = rel_l2(reference.logits(params, dims, ids, mask, (0, T), fault=fault), clean, mask)
    assert moved > 100 * TOL, (fault, moved)
    assert rel_l2(system_logits(params, ids, mask), clean, mask) < TOL


@pytest.mark.parametrize("fault", reference.MTP_FAULTS)
def test_planted_fault_of_the_module_moves_its_logits_and_only_its(fault):
    params, (ids, mask) = seeded_params(5), batch(5)
    dims = dims_of(CFG)
    clean = reference.mtp_logits(params, dims, ids, mask, (0, T - 1))
    moved = rel_l2(reference.mtp_logits(params, dims, ids, mask, (0, T - 1), fault=fault), clean, mask[:, :-1])
    assert moved > 100 * TOL, (fault, moved)
    assert rel_l2(system_mtp_logits(params, ids, mask), clean, mask[:, :-1]) < TOL
    stack = reference.logits(params, dims, ids, mask, (0, T))
    assert rel_l2(reference.logits(params, dims, ids, mask, (0, T), fault=fault), stack, mask) == 0.0


def test_faults_the_stand_in_weights_cannot_show_are_named():
    """On the benchmark's stand-in weights every norm scale is 1 but the
    per-head q and k scales: a final norm read from the wrong place, or a
    hidden state normed once too often, then changes almost nothing. NOT
    caught there, caught here where the scales scatter (the test above)."""
    fresh = CausalTransformer(CFG).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    ids, mask = batch(5)
    dims = dims_of(CFG)
    clean = reference.mtp_logits(fresh, dims, ids, mask, (0, T - 1))
    for fault, limit in (("mtp_shared_final_norm", 1e-6), ("mtp_post_final_norm_hidden", 1e-3)):
        moved = rel_l2(reference.mtp_logits(fresh, dims, ids, mask, (0, T - 1), fault=fault), clean, mask[:, :-1])
        assert moved < limit, (fault, moved)


def test_left_padded_row_is_the_row_alone():
    params, (ids, mask) = seeded_params(3), batch(3)
    alone = system_logits(params, ids[2:, 10:], mask[2:, 10:])
    assert rel_l2(system_logits(params, ids, mask)[2:, 10:], alone, mask[2:, 10:]) < 1e-5


def test_hydra_branch_replays_the_top_and_never_the_module():
    params, (ids, mask) = seeded_params(4), batch(4)
    model = CausalTransformer(CFG)
    for branch_layer in (1, 5):
        full = model.apply({"params": params}, ids, attention_mask=mask, branch_layer=branch_layer)
        top = model.apply({"params": params}, full["branch_input"], branch_layer, mask,
                          method=CausalTransformer.forward_branch)
        assert rel_l2(top["logits"], full["logits"], mask) < 1e-6
    without = {k: v for k, v in params.items() if k != "mtp_0"}  # no forward but `draft` reads it
    assert rel_l2(system_logits(without, ids, mask), system_logits(params, ids, mask), mask) == 0.0


def test_flash_path_agrees_with_the_einsum_path():
    """A window of 8 under the kernel's tile, and the unroped global layer."""
    params, (ids, mask) = seeded_params(1), batch(1)
    flash = dataclasses.replace(CFG, attention_impl="pallas")
    assert rel_l2(system_logits(params, ids, mask, flash), system_logits(params, ids, mask), mask) < TOL
    assert rel_l2(system_mtp_logits(params, ids, mask, flash), system_mtp_logits(params, ids, mask), mask[:, :-1]) < TOL


# ---------------------------------------------------------------------------
# loss and gradients of a PPO step's objective
# ---------------------------------------------------------------------------


def test_loss_and_gradients_of_a_ppo_objective_match_reference():
    """The clipped surrogate on fixed advantages and old logprobs, through the
    system's forward and through the reference's: the loss, and its gradient
    with respect to every leaf the loss reads. The module's leaves get none."""
    params, (ids, mask) = seeded_params(7, LORA), batch(7)
    dims = dims_of(LORA)
    rs = np.random.RandomState(7)
    P = 16
    adv = jnp.asarray(rs.randn(B, T - P), jnp.float32)
    old = jnp.asarray(-5.5 + 0.1 * rs.randn(B, T - P), jnp.float32)

    def objective(logits):
        lp = jnp.take_along_axis(jax.nn.log_softmax(logits[:, P - 1 : -1]), ids[:, P:, None], axis=-1)[..., 0]
        ratio = jnp.exp(lp - old)
        loss = jnp.maximum(-adv * ratio, -adv * jnp.clip(ratio, 0.8, 1.2))
        return jnp.sum(loss * mask[:, P:]) / jnp.sum(mask[:, P:])

    sys_loss, sys_grad = jax.value_and_grad(lambda p: objective(system_logits(p, ids, mask, LORA)))(params)
    ref_loss, ref_grad = jax.value_and_grad(lambda p: objective(reference.logits(p, dims, ids, mask, (0, T))))(params)
    assert abs(float(sys_loss) - float(ref_loss)) < 1e-5 * abs(float(ref_loss))
    for (path, g), want in zip(jax.tree_util.tree_leaves_with_path(sys_grad), jax.tree_util.tree_leaves(ref_grad)):
        name = jax.tree_util.keystr(path)
        if "mtp_0" in name:
            assert float(jnp.max(jnp.abs(g))) == 0.0 and float(jnp.max(jnp.abs(want))) == 0.0, name
            continue
        scale = float(jnp.linalg.norm(want))
        assert float(jnp.linalg.norm(g - want)) <= 2e-3 * scale + 1e-7, (name, scale)


# ---------------------------------------------------------------------------
# the sampler's cache: rings shorter than the row, one token or a span a row
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [CFG, LORA], ids=["plain", "adapters"])
@pytest.mark.parametrize("prompt", [5, 21])
def test_prefill_then_decode_through_the_rings_matches_reference_full_forward(prompt, cfg):
    """The prefill leaves its last 9 positions in each ring; then one token at
    a time to 40: logits at every position against the reference's full
    forward. The ring holds one slot more than the window, which the bias
    must hide."""
    params, (ids, mask) = seeded_params(6, cfg), batch(6)
    model = CausalTransformer(cfg)
    want = reference.logits(params, dims_of(cfg), ids, mask, (0, T))
    slots = jnp.concatenate([mask[:, :prompt], jnp.zeros((B, T - prompt), jnp.int32)], axis=1)
    step = jax.jit(lambda ids_, slots_, cache_, at: model.apply(
        {"params": params}, ids_, attention_mask=slots_, cache=cache_, cache_index=at))
    out = model.apply({"params": params}, ids[:, :prompt], attention_mask=slots,
                      cache=make_kv_cache(cfg, B, T), cache_index=jnp.asarray(0, jnp.int32))
    assert rel_l2(out["logits"], want[:, :prompt], mask[:, :prompt]) < TOL
    for t in range(prompt, T):
        slots = slots.at[:, t].set(mask[:, t])
        out = step(ids[:, t : t + 1], slots, out["cache"], jnp.asarray(t))
        assert [layer["k"].shape[1] for layer in out["cache"]] == [9] * 4 + [T, T]
        assert rel_l2(out["logits"], want[:, t : t + 1], mask[:, t : t + 1]) < TOL, t


def test_two_token_verify_at_each_rows_own_index_matches_reference():
    """Speculation's verify: a span of two tokens a row, each row at a depth
    of its own, written into the rings and read back under the window. A
    rejected second token's slot is invalid in the next round's mask and is
    written over."""
    params, (ids, mask) = seeded_params(8), batch(8)
    model = CausalTransformer(CFG)
    want = reference.logits(params, dims_of(CFG), ids, mask, (0, T))
    prompt = 12
    slots = jnp.concatenate([mask[:, :prompt], jnp.zeros((B, T - prompt), jnp.int32)], axis=1)
    out = model.apply({"params": params}, ids[:, :prompt], attention_mask=slots,
                      cache=make_kv_cache(CFG, B, T), cache_index=jnp.asarray(0, jnp.int32))
    cache = out["cache"]
    at = np.full((B,), prompt)
    advance = [(2, 1, 2), (1, 2, 2), (2, 2, 1)]  # tokens a row commits a round: unlike depths from round 1 on
    verify = jax.jit(lambda ids_, slots_, cache_, at_: model.apply(
        {"params": params}, ids_, attention_mask=slots_, cache=cache_, cache_index=at_))
    for round_ in range(11):
        rows = np.arange(B)
        span = jnp.stack([ids[rows, at], ids[rows, at + 1]], axis=1)
        committed = (np.arange(T)[None, :] < (at + 2)[:, None]).astype(np.int32) * np.asarray(mask)
        out = verify(span, jnp.asarray(committed), cache, jnp.asarray(at, jnp.int32))
        cache = out["cache"]
        for b in range(B):
            got, ref = out["logits"][b], want[b, at[b] : at[b] + 2]
            assert float(jnp.linalg.norm(got - ref)) < TOL * float(jnp.linalg.norm(ref)), (round_, b)
        at = at + np.asarray(advance[round_ % 3])
    assert at.min() > prompt + 9 + 2 and len(set(at.tolist())) > 1  # every ring wrapped, rows at unlike depths


def test_ring_refuses_a_span_it_cannot_hold_by_name():
    plain = dataclasses.replace(CFG, mtp_layers=0)  # rings of the window alone
    params, (ids, mask) = seeded_params(2, plain), batch(2)
    with pytest.raises(NotImplementedError, match="a span of 2 tokens at each row's own index into a ring cache of 8"):
        CausalTransformer(plain).apply({"params": params}, ids[:, 12:14], attention_mask=mask,
                                       cache=make_kv_cache(plain, B, T), cache_index=jnp.full((B,), 12, jnp.int32))


# ---------------------------------------------------------------------------
# the self-drafting sampler against the reference
# ---------------------------------------------------------------------------


def self_draft(params, ids, mask, rng, config, cfg=CFG):
    model = CausalTransformer(cfg)
    apply = lambda p, i, **kw: model.apply({"params": p}, i, **kw)
    drafter = module_drafter(lambda p, h, n, **kw: model.apply({"params": p}, h, n, method="draft", **kw))
    return generate_speculative(
        apply, params, None, params, lambda b, s: make_kv_cache(cfg, b, s)[:L], lambda b, s: make_kv_cache(cfg, b, s)[L:],
        ids, mask, rng, config, gamma=1, return_stats=True, drafter=drafter)


def test_self_draft_records_the_references_logprobs_and_counts_its_acceptance():
    """Sampling at temperature 1 from 14-token left-padded prompts for 26
    tokens, through rings of 9: the logprob recorded for each token is the
    reference's on the finished row, and the acceptances counted are, in
    expectation, the reference's ``sum_x min(p(x), q(x))`` summed over the
    positions drafted."""
    params, (ids, _) = seeded_params(9), batch(9, rows=8)
    P, N = 14, T - 14
    mask = jnp.asarray(np.arange(T)[None, :] >= (3 * (np.arange(8) % 4))[:, None], jnp.int32)  # 0, 3, 6, 9 pads
    config = GenerationConfig(max_new_tokens=N, eos_token_id=None, pad_token_id=0)
    out, stats = jax.jit(lambda r: self_draft(params, ids[:, :P], mask[:, :P], r, config))(jax.random.PRNGKey(1))
    full_mask = jnp.concatenate([mask[:, :P], out.response_mask], axis=1)
    dims = dims_of(CFG)
    want = reference.logits(params, dims, out.sequences, full_mask, (P - 1, T - 1))
    want_lp = jnp.take_along_axis(jax.nn.log_softmax(want), out.response_tokens[..., None], axis=-1)[..., 0]
    assert float(jnp.max(jnp.abs(want_lp - out.response_logprobs))) < 1e-4
    assert int(jnp.sum(out.response_mask)) == 8 * N
    # p for slot c from the stack at c - 1, q from the module's entry c - 2
    p = jax.nn.softmax(want, axis=-1)
    q = jax.nn.softmax(reference.mtp_logits(params, dims, out.sequences, full_mask, (P - 2, T - 2)), axis=-1)
    overlap = float(jnp.mean(jnp.sum(jnp.minimum(p, q), axis=-1)))
    counted = float(stats["accepted_draft_tokens"]) / float(stats["proposed_draft_tokens"])
    assert float(stats["proposed_draft_tokens"]) == float(stats["live_row_rounds"]) > 100
    assert abs(counted - overlap) < 0.1, (counted, overlap)  # 150 to 200 draws: three sigma of a share near 0.3
    assert abs(float(stats["tokens_per_round"]) - (1 + counted)) < 0.05


# ---------------------------------------------------------------------------
# one chip's share of the experts, the shared expert counted once
# ---------------------------------------------------------------------------


def test_the_held_shares_add_up_to_the_uncut_layer():
    """Four chips hold experts 0-1, 2-3, 4-5, 6-7 of one layer (the toy of the
    deployment's sixteen shares of 8 of 128), the same router and the same
    shared expert. The routed parts and ONE shared part sum to what the uncut
    reference gives for the whole layer."""
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
    routed_total = 0.0
    for first in range(0, E, 2):
        share = dataclasses.replace(CFG, moe_experts_held=2, moe_first_expert=first)
        mine = {"router": whole["router"], "shared_expert": whole["shared_expert"],
                **{k: whole[k][first : first + 2] for k in ("w_gate", "w_up", "w_down")}}
        y, _ = MoEMLP(share).apply({"params": mine}, n, mask)
        routed_part, shared_part = reference.moe_layer(mine, n, K, scaling, first=first)
        assert rel_l2(y, routed_part + shared_part, mask) < TOL
        routed_total = routed_total + (y - shared_part)  # every chip computes the shared expert alike
    assert rel_l2(routed_total + shared_want, routed_want + shared_want, mask) < TOL
    y_all, _ = MoEMLP(CFG).apply({"params": whole}, n, mask)
    assert rel_l2(y_all, routed_want + shared_want, mask) < TOL


# ---------------------------------------------------------------------------
# the preset and the configuration file
# ---------------------------------------------------------------------------

PUBLISHED = {  # the catalog row's `config`, by TransformerConfig field
    "hidden_size": 6144, "intermediate_size": 18432, "moe_intermediate_size": 2048, "expert_width": 2048,
    "num_heads": 64, "kv_heads": 8, "head_dim": 128, "dims_per_head": 128, "num_experts": 128,
    "num_experts_per_tok": 8, "num_shared_experts": 1, "first_k_dense": 1, "moe_renormalize": True,
    "routed_scaling_factor": 2.5, "num_layers": 48, "layer_norm_epsilon": 1e-5, "rope_theta": 1000000.0,
    "max_position_embeddings": 262144, "tie_word_embeddings": False, "vocab_size": 153600,
    "activation": "silu", "attn_bias": False, "model_type": "exaone_moe", "moe_scoring": "sigmoid",
    "sliding_window": 128, "mtp_layers": 1, "qk_norm": "head",
}


@pytest.mark.parametrize("field", sorted(PUBLISHED))
def test_preset_holds_the_published_value(field):
    big = config_from_spec("builtin:k-exaone-236b-a23b")
    assert getattr(big, field) == PUBLISHED[field]
    assert hash(big) == hash(config_from_spec("builtin:k-exaone-236b-a23b"))


def test_the_cut_is_the_configuration_files_and_its_widths_check():
    from chipbench import job
    from trlx_tpu.data.configs import ModelConfig, ParallelConfig

    big = config_from_spec("builtin:k-exaone-236b-a23b")
    kinds = [(l.window, l.rotary, l.ffn) for l in big.layer_layouts[:8]]
    assert kinds == [(128, True, "dense")] + [(128, True, "moe")] * 2 + [(None, False, "moe")] + \
        [(128, True, "moe")] * 3 + [(None, False, "moe")]
    file = job.load_config("k-exaone-236b-a23b-l5e8")
    model = file["job"]["model"]
    cut = config_from_spec(model["model_path"], **model["model_extra_kwargs"])
    assert (cut.num_layers, cut.first_k_dense, cut.experts_held, cut.num_experts, cut.vocab_size, cut.mtp_layers) == (
        5, 1, 8, 128, 19200, 1)
    assert [(l.window, l.ffn) for l in cut.layer_layouts] == [(128, "dense")] + [(128, "moe")] * 3 + [(None, "moe")]
    assert file["published"]["num_experts"] == 8 and file["router_width"] == 128
    assert sorted(file["reduced"]) == ["layer_types", "mlp_layer_types", "num_experts", "num_hidden_layers",
                                       "sliding_windows", "vocab_size"]
    assert "expert-parallel 16" in file["parallelism"] and "vocabulary-parallel 8" in file["parallelism"]
    # every number of the catalog row under its own key, but the six cut ones
    import json

    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "K-EXAONE-236B-A23B")
    differs = sorted(k for k, v in row["config"].items() if file["published"].get(k) != v)
    assert differs == sorted(file["reduced"])
    cfg = types.SimpleNamespace(model=ModelConfig(**model), parallel=ParallelConfig(**file["job"]["parallel"]))
    job.check_published_widths(cfg, file)
    # 3033 M parameters at this cut (the configuration file's arithmetic), 529 M of them the module
    shapes = jax.eval_shape(lambda: CausalTransformer(cut).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    assert abs(count(shapes) / 1e6 - 3033) < 2 and abs(count(shapes["mtp_0"]) / 1e6 - 529.3) < 0.5
    cache = jax.eval_shape(lambda: make_kv_cache(cut, 64, 1281))
    assert [layer["k"].shape[1] for layer in cache] == [129] * 4 + [1281, 1281]


def test_hf_interop_says_there_is_no_converter():
    from trlx_tpu.models.hf_interop import config_from_hf

    with pytest.raises(ValueError, match="exaone_moe.*no HF checkpoint conversion"):
        config_from_hf(types.SimpleNamespace(model_type="exaone_moe"))


@pytest.mark.parametrize("what", ["two_modules", "latent", "scan"])
def test_config_refuses_a_module_it_has_not_built_by_name(what):
    bad = {"two_modules": dict(mtp_layers=2), "scan": dict(scan_layers=True),
           "latent": dict(kv_lora_rank=16, q_lora_rank=32, qk_nope_head_dim=8, qk_rope_head_dim=8, qk_norm=False)}[what]
    with pytest.raises(NotImplementedError, match="mtp_layers: ONE next-token-prediction module.*B6"):
        dataclasses.replace(CFG, **bad)


# ---------------------------------------------------------------------------
# trlx_tpu.train(): the normal PPO path with adapters, rollouts self-drafted
# ---------------------------------------------------------------------------


def _train_config(tmp_path, **model):
    from trlx_tpu.data.default_configs import default_ppo_config

    return default_ppo_config().evolve(
        train=dict(seq_length=36, batch_size=4, total_steps=2, eval_interval=10,
                   checkpoint_interval=10, epochs=1, save_best=False, tracker=None,
                   checkpoint_dir=str(tmp_path / "ckpts"), logging_dir=str(tmp_path / "logs")),
        model=dict(model_path="builtin:k-exaone-test",
                   model_extra_kwargs=dict(moe_experts_held=2, moe_first_expert=2), **model),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        parallel=dict(param_dtype="float32", compute_dtype="float32"),
        method=dict(num_rollouts=8, chunk_size=8, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=16, min_new_tokens=16, top_k=0, top_p=1.0, do_sample=True)),
    )


def test_train_runs_ppo_with_adapters_on_self_drafted_rollouts(tmp_path):
    """``trlx_tpu.train()`` with PPO, a value head, the hydra branch over the
    last block and LoRA: the model's own key makes the sampler draft with the
    module. The collection record counts rounds, proposals and acceptances;
    after two steps the last block's adapters and the value head have changed
    and nothing else has, the module least of all."""
    import trlx_tpu.trlx as trlx

    records, before = [], {}

    def hook(trainer):
        trainer.tracker = types.SimpleNamespace(
            log=lambda stats, step=None: records.append(dict(stats)), finish=lambda: None)
        before.update(params=jax.tree_util.tree_map(np.asarray, trainer.state.params))

    rng = np.random.RandomState(0)
    prompts = ["".join(chr(97 + c) for c in rng.randint(0, 26, size=20)) for _ in range(8)]
    config = _train_config(tmp_path, num_layers_unfrozen=1, peft_kwargs=dict(
        peft_type="lora", r=4, lora_alpha=8, modified_modules=["q_proj", "k_proj", "v_proj", "o_proj"]))
    trainer = trlx.train(
        reward_fn=lambda samples, prompts, outputs, **kw: [float(i % 4) for i, _ in enumerate(outputs)],
        prompts=prompts, config=config, init_trainer_hook=hook)
    assert trainer.tcfg.model_type == "exaone_moe" and trainer.self_drafts and trainer.draft_gamma == 1
    c = next(r for r in records if "time/exp" in r)
    assert float(c.get("policy/sqrt_kl", c.get("policy/sqrt_ref_kl"))) < 1e-6
    rounds, proposed, accepted = c["rollout/spec_rounds"], c["rollout/draft_proposed"], c["rollout/draft_accepted"]
    assert 8 <= rounds <= 16 and 8 * 8 <= proposed <= 8 * rounds and 0 <= accepted <= proposed
    assert c["rollout/decode_steps"] == 16.0  # the longest response in TOKENS
    assert abs(c["rollout/tokens_per_round"] - 8 * 16 / proposed) < 1e-6
    assert abs(c["rollout/padded_decode_frac"] - (1 - proposed / (8 * rounds))) < 1e-6
    assert abs(c["time/spec_round"] - c["time/generate"] / rounds) < 1e-9
    S = int(c["rollout/mtp_cache_bytes"] // (2 * 8 * 2 * 16 * 4))  # the padded prompt, new tokens, the draft's slot
    assert 20 + 16 + 1 <= S <= 24 + 16 + 1 and c["rollout/mtp_cache_bytes"] == 2 * 8 * S * 2 * 16 * 4
    assert 0.0 < c["rollout/kv_read_frac"] < 1.0  # four rings of 9 read whole, a global layer of the row
    step = next(r for r in records if "time/train_step" in r)
    assert np.isfinite([v for k, v in step.items() if k.startswith("losses/")]).all()
    assert step["learn/grad_param_frac"] < 0.2
    after = jax.tree_util.tree_map(np.asarray, trainer.state.params)
    changed = {jax.tree_util.keystr(path)
               for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(after), jax.tree_util.tree_leaves(before["params"]))
               if not np.array_equal(a, b)}
    assert changed and all("v_head" in k or ("['h_4']" in k and "lora_" in k) for k in changed), changed


def test_a_draft_model_beside_the_module_is_refused_by_name(tmp_path):
    from trlx_tpu.trainer.ppo import PPOTrainer

    config = _train_config(tmp_path, draft_model_path="builtin:gpt2-test")
    with pytest.raises(ValueError, match="model.draft_model_path 'builtin:gpt2-test' beside a model that drafts with its own"):
        PPOTrainer(config, reward_fn=lambda samples, **kw: [0.0] * len(samples))
