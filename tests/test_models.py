"""Model-layer tests (shape of the reference's ``tests/test_models.py``):
HF logit-parity contract tests per family, cache/decode parity, hydra branch,
heads, freezing masks, generation."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.data.configs import ModelConfig
from trlx_tpu.models.builder import (
    build_causal_lm,
    hydra_ref_params,
    trainable_mask,
)
from trlx_tpu.models.heads import (
    CausalLMWithILQLHeads,
    CausalLMWithValueHead,
    sync_target_q_params,
)
from trlx_tpu.models import transformer
from trlx_tpu.models.transformer import Attention, CausalTransformer, TransformerConfig, alibi_slopes
from trlx_tpu.models import hf_interop
from trlx_tpu.ops.sampling import GenerationConfig, generate

jax.config.update("jax_default_matmul_precision", "highest")


def _f32(cfg: TransformerConfig) -> TransformerConfig:
    return cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32})


def _tiny_hf(family: str):
    """Build a tiny random torch model + converted params + flax config."""
    import torch
    import transformers as tf

    torch.manual_seed(0)
    if family == "gpt2":
        hf = tf.GPT2LMHeadModel(tf.GPT2Config(vocab_size=97, n_positions=64, n_embd=32, n_layer=2, n_head=4))
    elif family == "llama":
        hf = tf.LlamaForCausalLM(
            tf.LlamaConfig(
                vocab_size=97, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=64, max_position_embeddings=64,
                tie_word_embeddings=False,
            )
        )
    elif family == "gpt_neox":
        hf = tf.GPTNeoXForCausalLM(
            tf.GPTNeoXConfig(
                vocab_size=97, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
                intermediate_size=128, max_position_embeddings=64, rotary_pct=0.25,
                use_parallel_residual=True,
            )
        )
    elif family == "gptj":
        hf = tf.GPTJForCausalLM(tf.GPTJConfig(vocab_size=97, n_positions=64, n_embd=32, n_layer=2, n_head=4, rotary_dim=8))
    elif family == "opt":
        hf = tf.OPTForCausalLM(
            tf.OPTConfig(
                vocab_size=97, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
                ffn_dim=128, max_position_embeddings=64, word_embed_proj_dim=32,
            )
        )
    elif family == "bloom":
        hf = tf.BloomForCausalLM(tf.BloomConfig(vocab_size=97, hidden_size=32, n_layer=2, n_head=4))
    elif family == "mistral":
        # sliding_window=8 < T=12 in the parity tests: the windowed masking
        # itself is checked against HF's own implementation
        hf = tf.MistralForCausalLM(
            tf.MistralConfig(
                vocab_size=97, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=64, max_position_embeddings=64,
                sliding_window=8, tie_word_embeddings=False, attn_implementation="eager",
            )
        )
    elif family == "mixtral":
        hf = tf.MixtralForCausalLM(
            tf.MixtralConfig(
                vocab_size=97, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=64, max_position_embeddings=64,
                num_local_experts=4, num_experts_per_tok=2, sliding_window=None,
                tie_word_embeddings=False,
            )
        )
    elif family == "olmoe":
        hf = tf.OlmoeForCausalLM(
            tf.OlmoeConfig(
                vocab_size=97, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=4, intermediate_size=24, max_position_embeddings=64,
                num_experts=8, num_experts_per_tok=3, tie_word_embeddings=False,
            )
        )
    else:
        raise ValueError(family)
    hf.eval()
    params, cfg = hf_interop.params_from_hf(hf)
    return hf, params, _f32(cfg)


@pytest.mark.parametrize("family", ["gpt2", "llama", "gpt_neox", "gptj", "opt", "bloom", "mistral", "mixtral", "olmoe"])
def test_hf_logit_parity(family):
    """The flax decoder reproduces the torch reference logits exactly."""
    import torch

    hf, params, cfg = _tiny_hf(family)
    model = CausalTransformer(cfg)
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 12))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    got = np.asarray(model.apply({"params": params["backbone"]}, jnp.array(ids))["logits"])
    assert np.abs(got - ref).max() < 2e-4


def _setup_value_model():
    module, params, tcfg = build_causal_lm(ModelConfig("builtin:gpt2-test"), head="value")
    tcfg = _f32(tcfg)
    return CausalLMWithValueHead(tcfg), params, tcfg


def _padded_batch(vocab=250, B=3, P=8):
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (B, P)).astype(np.int32)
    mask = np.ones((B, P), np.int32)
    mask[0, :3] = 0
    mask[2, :5] = 0
    ids[mask == 0] = 258
    return jnp.array(ids), jnp.array(mask)


def test_cache_decode_matches_full_forward():
    module, params, tcfg = _setup_value_model()
    ids, mask = _padded_batch()
    B, P = ids.shape

    apply_fn = lambda p, i, **kw: module.apply({"params": p}, i, **kw)
    full = apply_fn(params, ids, attention_mask=mask)

    S = P + 1
    cache = module.apply({"params": params}, method=module.init_cache, batch_size=B, max_length=S, dtype=jnp.float32)
    slot_mask = jnp.concatenate([mask, jnp.zeros((B, 1), jnp.int32)], axis=1)
    pre = apply_fn(params, ids, attention_mask=slot_mask, cache=cache, cache_index=jnp.asarray(0))
    # parity on real positions (pad positions attend nothing → undefined)
    diff = np.abs(np.asarray(pre["logits"]) - np.asarray(full["logits"])).max(axis=2)
    assert diff[np.asarray(mask) > 0].max() < 1e-4

    # one decode step == full forward on the extended sequence
    nxt = jnp.array([5, 7, 9], jnp.int32)
    full2 = apply_fn(
        params,
        jnp.concatenate([ids, nxt[:, None]], axis=1),
        attention_mask=jnp.concatenate([mask, jnp.ones((B, 1), jnp.int32)], axis=1),
    )
    slot_mask2 = slot_mask.at[:, P].set(1)
    plen = jnp.sum(mask, axis=1)
    dec = apply_fn(
        params,
        nxt[:, None],
        attention_mask=slot_mask2,
        positions=plen[:, None],
        cache=pre["cache"],
        cache_index=jnp.asarray(P),
    )
    assert np.abs(np.asarray(dec["logits"][:, 0]) - np.asarray(full2["logits"][:, -1])).max() < 1e-4
    assert np.abs(np.asarray(dec["value"][:, 0]) - np.asarray(full2["value"][:, -1])).max() < 1e-4


def test_generate_greedy_matches_naive_decode():
    module, params, tcfg = _setup_value_model()
    ids, mask = _padded_batch()
    B, P = ids.shape
    N = 5

    apply_fn = lambda p, i, **kw: module.apply({"params": p}, i, **kw)
    init_cache_fn = lambda b, s: module.apply(
        {"params": params}, method=module.init_cache, batch_size=b, max_length=s, dtype=jnp.float32
    )
    cfg = GenerationConfig(max_new_tokens=N, do_sample=False, eos_token_id=None, pad_token_id=258)
    gen = jax.jit(partial(generate, apply_fn, params, init_cache_fn, config=cfg))
    out = gen(input_ids=ids, attention_mask=mask, rng=jax.random.PRNGKey(0))

    toks, m = np.asarray(ids), np.asarray(mask)
    for _ in range(N):
        o = apply_fn(params, jnp.array(toks), attention_mask=jnp.array(m))
        nt = np.asarray(o["logits"][:, -1].argmax(-1)).astype(np.int32)
        toks = np.concatenate([toks, nt[:, None]], axis=1)
        m = np.concatenate([m, np.ones((toks.shape[0], 1), np.int32)], axis=1)
    assert (np.asarray(out.response_tokens) == toks[:, P:]).all()
    assert out.response_mask.sum() == out.response_mask.size  # no eos → all live


def test_mistral_window_decode_matches_full_forward():
    """KV-cache decode with sliding-window attention (mistral family): the
    generated sequence grows past the window (8), and each cached decode step
    must match the windowed full forward."""
    from trlx_tpu.models.transformer import make_kv_cache

    module, params, tcfg = build_causal_lm(
        ModelConfig(
            "builtin:mistral-test",
            model_extra_kwargs=dict(dtype=jnp.float32, param_dtype=jnp.float32),
        ),
        head="value",
    )
    assert tcfg.sliding_window == 8
    ids, mask = _padded_batch(vocab=250)
    B, P = ids.shape
    N = 6  # prompt(8) + 6 > window(8): the window slides during decode

    apply_fn = lambda p, i, **kw: module.apply({"params": p}, i, **kw)
    cfg = GenerationConfig(max_new_tokens=N, do_sample=False, eos_token_id=None, pad_token_id=258)
    gen = partial(generate, apply_fn, params, lambda b, s: make_kv_cache(tcfg, b, s, jnp.float32), config=cfg)
    out = gen(input_ids=ids, attention_mask=mask, rng=jax.random.PRNGKey(0))

    toks, m = np.asarray(ids), np.asarray(mask)
    for _ in range(N):
        o = apply_fn(params, jnp.array(toks), attention_mask=jnp.array(m))
        nt = np.asarray(o["logits"][:, -1].argmax(-1)).astype(np.int32)
        toks = np.concatenate([toks, nt[:, None]], axis=1)
        m = np.concatenate([m, np.ones((toks.shape[0], 1), np.int32)], axis=1)
    assert (np.asarray(out.response_tokens) == toks[:, P:]).all()


def test_generate_eos_early_stop():
    module, params, tcfg = _setup_value_model()
    ids, mask = _padded_batch()
    apply_fn = lambda p, i, **kw: module.apply({"params": p}, i, **kw)
    init_cache_fn = lambda b, s: module.apply(
        {"params": params}, method=module.init_cache, batch_size=b, max_length=s, dtype=jnp.float32
    )
    # pick the first greedy token of sample 0 as "eos" so it stops immediately
    first = int(
        np.asarray(apply_fn(params, ids, attention_mask=mask)["logits"][0, -1].argmax())
    )
    cfg = GenerationConfig(max_new_tokens=4, do_sample=False, eos_token_id=first, pad_token_id=258)
    out = jax.jit(partial(generate, apply_fn, params, init_cache_fn, config=cfg))(
        input_ids=ids, attention_mask=mask, rng=jax.random.PRNGKey(0)
    )
    rm = np.asarray(out.response_mask)
    rt = np.asarray(out.response_tokens)
    assert rt[0, 0] == first and rm[0, 0] == 1
    assert rm[0, 1:].sum() == 0  # stopped after eos
    assert (rt[0, 1:] == 258).all()  # padded after eos
    # mask is contiguous (no holes)
    for row in rm:
        on = row.nonzero()[0]
        assert len(on) == 0 or (on == np.arange(on[0], on[0] + len(on))).all()


def test_hydra_branch_consistency():
    """forward(branch_layer=k) + forward_branch(ref=same params) == full logits."""
    module, params, tcfg = _setup_value_model()
    ids, mask = _padded_batch()
    out = module.apply({"params": params}, ids, attention_mask=mask, branch_layer=1)
    branch = module.apply(
        {"params": params},
        out["branch_input"],
        1,
        mask,
        method=module.forward_branch,
    )
    diff = np.abs(np.asarray(branch["logits"]) - np.asarray(out["logits"])).max(axis=2)
    assert diff[np.asarray(mask) > 0].max() < 1e-4


def test_hydra_ref_params_subtree():
    module, params, tcfg = build_causal_lm(ModelConfig("builtin:gpt2-test"), head="value")
    ref = hydra_ref_params(params, tcfg, 1)
    assert set(ref) == {"h_1", "ln_f", "wte"}  # top block + norm + tied head


def test_trainable_mask_freezing():
    module, params, tcfg = build_causal_lm(ModelConfig("builtin:gpt2-test"), head="value")
    mask = trainable_mask(params, tcfg, num_layers_unfrozen=1)
    leaves_h0 = jax.tree_util.tree_leaves(mask["backbone"]["h_0"])
    leaves_h1 = jax.tree_util.tree_leaves(mask["backbone"]["h_1"])
    assert not any(leaves_h0) and all(leaves_h1)
    assert all(jax.tree_util.tree_leaves(mask["v_head"]))
    # -1 unfreezes everything
    mask_all = trainable_mask(params, tcfg, num_layers_unfrozen=-1)
    assert all(jax.tree_util.tree_leaves(mask_all))


def test_ilql_heads_and_target_sync():
    module, params, tcfg = build_causal_lm(ModelConfig("builtin:gpt2-test"), head="ilql")
    ids, mask = _padded_batch()
    out = module.apply({"params": params}, ids, attention_mask=mask)
    assert len(out["qs"]) == 2 and len(out["target_qs"]) == 2
    assert out["qs"][0].shape == (*ids.shape, tcfg.vocab_size)
    assert out["vs"].shape == (*ids.shape, 1)

    # polyak: alpha=1 copies q → target exactly
    synced = sync_target_q_params(params, alpha=1.0)
    q = jax.tree_util.tree_leaves(synced["ilql_heads"]["q_head_0"])
    t = jax.tree_util.tree_leaves(synced["ilql_heads"]["target_q_head_0"])
    for a, b in zip(q, t):
        assert np.allclose(a, b)
    # alpha=0 leaves target untouched
    synced0 = sync_target_q_params(params, alpha=0.0)
    t_old = jax.tree_util.tree_leaves(params["ilql_heads"]["target_q_head_0"])
    t_new = jax.tree_util.tree_leaves(synced0["ilql_heads"]["target_q_head_0"])
    for a, b in zip(t_old, t_new):
        assert np.allclose(a, b)
    # target-q heads are masked out of training
    mask_tree = trainable_mask(params, tcfg, -1)
    assert not any(jax.tree_util.tree_leaves(mask_tree["ilql_heads"]["target_q_head_0"]))
    assert all(jax.tree_util.tree_leaves(mask_tree["ilql_heads"]["q_head_0"]))


def test_builder_vocab_override():
    module, params, tcfg = build_causal_lm(
        ModelConfig("builtin:gpt2-test", model_extra_kwargs={"vocab_size": 300})
    )
    assert tcfg.vocab_size == 300
    assert params["wte"]["embedding"].shape[0] == 300


def test_preset_flag_override():
    """model_extra_kwargs may override any preset field, incl. arch flags."""
    module, params, tcfg = build_causal_lm(
        ModelConfig("builtin:gpt2-test", model_extra_kwargs={"tie_word_embeddings": False})
    )
    assert tcfg.tie_word_embeddings is False
    assert "lm_head" in params


def test_ilql_target_heads_start_as_q_copies():
    module, params, tcfg = build_causal_lm(ModelConfig("builtin:gpt2-test"), head="ilql")
    q = jax.tree_util.tree_leaves(params["ilql_heads"]["q_head_0"])
    t = jax.tree_util.tree_leaves(params["ilql_heads"]["target_q_head_0"])
    for a, b in zip(q, t):
        assert np.allclose(a, b)


def test_pad_rows_left_truncation_keeps_tail():
    from trlx_tpu.pipeline.offline_pipeline import pad_rows

    out, mask = pad_rows([[1, 2, 3, 4, 5]], 0, "left", 1, fixed_length=3)
    assert out.tolist() == [[3, 4, 5]]  # keeps tokens adjacent to response
    out, _ = pad_rows([[1, 2, 3, 4, 5]], 0, "right", 1, fixed_length=3)
    assert out.tolist() == [[1, 2, 3]]


def _repeated_kv_attention(q, k, v, attention_bias, dtype):
    """The plain oracle: K and V repeated to every query head, then the two
    classic einsums."""
    reps = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, reps, axis=2), jnp.repeat(v, reps, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(jnp.asarray(q.shape[-1], dtype))
    probs = jax.nn.softmax((scores + attention_bias).astype(jnp.float32), axis=-1).astype(dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def _einsum_branch_case(case, H):
    """``(T, S, cache_index, bias [B, 1 | H, T, S])`` of one way into the
    einsum branch, B = 2, row 1 left-padded by one slot."""
    B, S = 2, 8
    T, ci = {"full": (S, None), "window": (S, None), "alibi": (S, None),
             "decode": (1, jnp.asarray(5)), "verify": (3, jnp.asarray([2, 5]))}[case]
    first = jnp.zeros((B,), jnp.int32) if ci is None else jnp.broadcast_to(ci, (B,))
    q_slots = first[:, None] + jnp.arange(T)[None, :]  # [B, T]
    k_slots = jnp.arange(S)[None, None, :]
    key_mask = jnp.ones((B, S), bool).at[1, 0].set(False)
    visible = (k_slots <= q_slots[:, :, None]) & key_mask[:, None, :]
    if case == "window":
        visible &= q_slots[:, :, None] - k_slots < 3
    bias = jnp.where(visible, 0.0, -1e9)[:, None]  # [B, 1, T, S]
    if case == "alibi":
        dist = (k_slots - q_slots[:, :, None]).astype(jnp.float32)  # [B, T, S]
        slopes = jnp.asarray(alibi_slopes(H), jnp.float32)
        bias = bias + jnp.where(visible[:, None], slopes[None, :, None, None] * dist[:, None], 0.0)
    return T, S, ci, bias


@pytest.mark.parametrize("case", ["full", "decode", "verify", "window", "alibi"])
@pytest.mark.parametrize("kv_heads", [8, 4, 2, 1])
def test_einsum_attention_consumes_unrepeated_kv(kv_heads, case, monkeypatch):
    """The dense einsum branch contracts over a grouped head axis: for every
    head layout (MHA, GQA 2 and 4, MQA) and every way into the branch (full
    pass, cached single-token step at a scalar ``cache_index``, speculative
    verify span at a ``[B]`` one, sliding window, per-head ALiBi bias) the
    output, the written cache and the gradients (``dk``, ``dv`` group-summed)
    match the repeated-K/V oracle within float32 rounding."""
    B, H, D = 2, 8, 4
    T, S, ci, bias = _einsum_branch_case(case, H)
    ks = jax.random.split(jax.random.PRNGKey(kv_heads), 6)
    close = partial(np.testing.assert_allclose, atol=2e-5, rtol=2e-5)

    # the two contractions themselves: gradients with respect to q, k, v
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, kv_heads, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, kv_heads, D), jnp.float32)

    def out_and_grads(attention):
        attend = lambda q, k, v: attention(q, k, v, bias, jnp.float32)
        return attend(q, k, v), jax.grad(lambda *a: jnp.sum(attend(*a) ** 2), argnums=(0, 1, 2))(q, k, v)

    jax.tree_util.tree_map(
        close, out_and_grads(transformer.grouped_einsum_attention), out_and_grads(_repeated_kv_attention)
    )

    # the Attention module around them: projections, cache write, o_proj
    cfg = TransformerConfig(
        vocab_size=11, hidden_size=H * D, num_layers=1, num_heads=H, intermediate_size=16,
        num_kv_heads=kv_heads, position_scheme="alibi" if case == "alibi" else "learned",
        dtype=jnp.float32, attention_impl="xla",
    )
    x = jax.random.normal(ks[3], (B, T, H * D), jnp.float32)
    positions = jnp.zeros((B, T), jnp.int32)  # read by rotary only
    cache = None if ci is None else {
        "k": jax.random.normal(ks[4], (B, S, kv_heads, D), jnp.float32),
        "v": jax.random.normal(ks[5], (B, S, kv_heads, D), jnp.float32),
    }
    attn = Attention(cfg)
    params = attn.init(jax.random.PRNGKey(0), x, bias, positions, cache, ci)

    def run(params, x):
        out, new_cache = attn.apply(params, x, bias, positions, cache, ci)
        return jnp.sum(out ** 2), (out, new_cache)

    got = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(params, x)
    monkeypatch.setattr(transformer, "grouped_einsum_attention", _repeated_kv_attention)
    want = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(params, x)
    jax.tree_util.tree_map(close, got, want)
