"""OLMoE (64 experts top-8 as published; 8 experts top-2 here) against the
plain float32 reference the benchmark keeps, ``chipbench/reference/olmoe.py``.

Toy size on the CPU, float32 parameters and activations on both sides, so
nothing flips an expert choice and the mathematics has to agree: dropless
routing, QK-norm, not-renormalised gates, left-padding positions, the dense
and the paged cache, and the gradients of the SFT loss.
"""

import dataclasses
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import olmoe as reference
from trlx_tpu.models.transformer import (
    CausalTransformer,
    MoEMLP,
    TransformerConfig,
    make_kv_cache,
    router_load_summary,
)
from trlx_tpu.ops.paged_kv import attach_block_table

# Relative L2 of the logits (or of a gradient leaf). Both sides compute in
# float32, the CPU's matmuls are exact float32, so what is left is the order
# of summation: grouped matmuls over rows sorted by expert and a weighted sum
# over a token's k outputs, against one dense pass per expert accumulated in
# expert order. That is a few float32 ulps a layer: measured 2.5e-7 to 2.7e-7
# on the logits of three seeds and 6.3e-7 on the worst gradient leaf. The
# same system with bfloat16 activations reads 1.4e-2, and the mildest planted
# fault (no_qk_norm) 0.16: both far outside, as they must be.
TOL = 1e-4

CFG = TransformerConfig.olmoe("test", param_dtype=jnp.float32, dtype=jnp.float32)
DIMS = {
    "num_hidden_layers": CFG.num_layers,
    "num_attention_heads": CFG.num_heads,
    "num_key_value_heads": CFG.kv_heads,
    "rms_norm_eps": CFG.layer_norm_epsilon,
    "rope_theta": CFG.rope_theta,
    "num_experts_per_tok": CFG.num_experts_per_tok,
}
MODEL = CausalTransformer(CFG)
B, T = 3, 24


def seeded_params(seed):
    """The module's own tree, refilled: matrices at 1/sqrt(fan_in) (the
    init's 0.02 would leave the router near uniform and the layers small
    beside the embedding), norm scales scattered about 1."""
    shapes = jax.eval_shape(
        lambda: MODEL.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    )
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rs = np.random.RandomState(seed)
    out = []
    for path, leaf in leaves:
        name = getattr(path[-1], "key", "")
        if name == "scale":
            x = 1.0 + 0.2 * rs.randn(*leaf.shape)
        elif name == "embedding":
            x = rs.randn(*leaf.shape)
        else:  # [in, out] kernels and [E, in, out] expert stacks
            x = rs.randn(*leaf.shape) / np.sqrt(leaf.shape[-2])
        out.append(jnp.asarray(x, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def batch(seed):
    """Left-padded rows: row i has 3 * i padding tokens."""
    rs = np.random.RandomState(seed)
    ids = rs.randint(0, CFG.vocab_size, (B, T))
    mask = np.ones((B, T), np.int32)
    for i in range(B):
        mask[i, : 3 * i] = 0
    return jnp.asarray(ids, jnp.int32), jnp.asarray(mask)


def rel_l2(a, b, weight=None):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if weight is not None:
        a, b = a * weight, b * weight
    return float(np.sqrt(((a - b) ** 2).sum() / (b**2).sum()))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_matches_reference(seed):
    params, (ids, mask) = seeded_params(seed), batch(seed)
    out = MODEL.apply({"params": params}, ids, attention_mask=mask)
    want = reference.logits(params, DIMS, ids, mask, (0, T))
    assert rel_l2(out["logits"], want, np.asarray(mask)[..., None]) < TOL
    assert float(out["router_load"][0]) == 0.0


def _paged_cache(slots, block_size=8):
    table_blocks = -(-slots // block_size)
    pool = make_kv_cache(CFG, 1 + B * table_blocks, block_size)
    table = 1 + jnp.arange(B * table_blocks, dtype=jnp.int32).reshape(B, table_blocks)
    return attach_block_table(pool, table)


@pytest.mark.parametrize("cache_kind", ["dense", "paged"])
def test_prefill_then_decode_matches_reference_full_forward(cache_kind):
    """Prefill of the first P tokens into the cache, then the rest one token
    at a time through it: every position's logits against the reference's
    one full forward (which has no cache)."""
    params, (ids, mask) = seeded_params(3), batch(3)
    P = 16
    want = reference.logits(params, DIMS, ids, mask, (0, T))
    cache = _paged_cache(T) if cache_kind == "paged" else make_kv_cache(CFG, B, T)

    def slot_mask(n):  # slots written so far, padding still masked
        return mask * (jnp.arange(T)[None, :] < n)

    out = MODEL.apply({"params": params}, ids[:, :P], attention_mask=slot_mask(P),
                      cache=cache, cache_index=jnp.asarray(0, jnp.int32))
    got = [out["logits"]]
    step = jax.jit(lambda c, tok, m, i: MODEL.apply(
        {"params": params}, tok, attention_mask=m, cache=c, cache_index=i))
    for t in range(P, T):
        out = step(out["cache"], ids[:, t : t + 1], slot_mask(t + 1), jnp.asarray(t, jnp.int32))
        got.append(out["logits"])
    got = jnp.concatenate(got, axis=1)
    assert rel_l2(got, want, np.asarray(mask)[..., None]) < TOL


def _sft_loss(logits, ids, mask):
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    w = (mask[:, 1:] * mask[:, :-1]).astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.sum(w)


def test_sft_gradients_match_reference():
    params, (ids, mask) = seeded_params(4), batch(4)
    got = jax.grad(lambda p: _sft_loss(
        MODEL.apply({"params": p}, ids, attention_mask=mask)["logits"], ids, mask))(params)
    want = jax.grad(lambda p: _sft_loss(
        reference.logits(p, DIMS, ids, mask, (0, T)), ids, mask))(params)
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        assert float(jnp.abs(w).max()) > 0, jax.tree_util.keystr(path)
        assert rel_l2(g, w) < TOL, jax.tree_util.keystr(path)


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_planted_fault_moves_the_logits(fault):
    """The control: each fault the benchmark plants in the reference is far
    outside the tolerance that the sound reference meets."""
    params, (ids, mask) = seeded_params(0), batch(0)
    got = MODEL.apply({"params": params}, ids, attention_mask=mask)["logits"]
    bad = reference.logits(params, DIMS, ids, mask, (0, T), fault=fault)
    assert rel_l2(got, bad, np.asarray(mask)[..., None]) > 100 * TOL


def _collapsed_router_layer():
    """One MoE layer whose router sends every token to experts 0..K-1: the
    inputs are positive, those columns +1 and the others -1."""
    E, K = CFG.num_experts, CFG.num_experts_per_tok
    rs = np.random.RandomState(5)
    x = jnp.asarray(np.abs(rs.randn(2, 16, CFG.hidden_size)), jnp.float32)
    params = dict(seeded_params(5)["h_0"]["mlp"])
    column = jnp.where(jnp.arange(E) < K, 1.0, -1.0) + 0.01 * jnp.arange(E)
    params["router"] = {"kernel": jnp.broadcast_to(column, (CFG.hidden_size, E))}
    return x, params


@pytest.mark.parametrize("capacity_factor,dropped", [(0.0, False), (1.25, True)])
def test_nothing_dropped_when_every_token_picks_the_same_experts(capacity_factor, dropped):
    E, K = CFG.num_experts, CFG.num_experts_per_tok
    x, params = _collapsed_router_layer()
    cfg = dataclasses.replace(CFG, moe_capacity_factor=capacity_factor)
    y, aux = MoEMLP(cfg).apply({"params": params}, x)
    dropped_frac, load = np.asarray(router_load_summary(aux, cfg))
    assert load == pytest.approx(E / K)  # K experts share everything
    if dropped:  # the counter counts: capacity 1.25 keeps K*G*1.25/E slots an expert
        assert dropped_frac > 0.5
        return
    assert dropped_frac == 0.0
    want = reference._sparse_mlp(params, x, K, None)
    assert rel_l2(y, want) < TOL


def test_padding_tokens_route_nowhere():
    E, K = CFG.num_experts, CFG.num_experts_per_tok
    rs = np.random.RandomState(6)
    x = jnp.asarray(rs.randn(2, 12, CFG.hidden_size), jnp.float32)
    mask = jnp.ones((2, 12), jnp.int32).at[0, :5].set(0)
    params = seeded_params(6)["h_0"]["mlp"]
    layer = MoEMLP(CFG)
    y, aux = layer.apply({"params": params}, x, mask)
    assert np.all(np.asarray(y)[0, :5] == 0.0)
    assert float(aux[2]) == 19 and float(aux[4]) == 19 * K  # real tokens, assignments
    # what the padding holds changes nothing: not the real tokens' outputs,
    # not the statistics, not the router's gradient
    x2 = x.at[0, :5].set(100.0)
    y2, aux2 = layer.apply({"params": params}, x2, mask)
    np.testing.assert_array_equal(np.asarray(y)[0, 5:], np.asarray(y2)[0, 5:])
    np.testing.assert_array_equal(np.asarray(aux), np.asarray(aux2))

    def router_grad(inputs):
        def f(p):
            out, a = layer.apply({"params": p}, inputs, mask)
            return jnp.sum(out**2) + a[0] + a[1]
        return jax.grad(f)(params)["router"]["kernel"]

    np.testing.assert_array_equal(np.asarray(router_grad(x)), np.asarray(router_grad(x2)))


@pytest.mark.parametrize("hit", [4, 8])
def test_suffix_only_prefill_is_the_full_prefill_bit_for_bit(hit):
    """What the prefix cache relies on: with dropless routing no token's
    result depends on the other tokens of its row or batch."""
    from trlx_tpu.models.builder import build_causal_lm
    from trlx_tpu.data.configs import ModelConfig, ParallelConfig

    module, params, tcfg = build_causal_lm(
        ModelConfig(model_path="builtin:olmoe-test"), ParallelConfig(data=1), seed=7)
    ids, mask = batch(7)
    P = 16
    ids, mask = ids[:, :P], mask[:, :P]

    def prefill(tokens, cache, at):
        return module.apply({"params": params}, tokens, attention_mask=mask, cache=cache,
                            cache_index=jnp.asarray(at, jnp.int32))

    full = prefill(ids, make_kv_cache(tcfg, B, P), 0)
    # the prefix's K and V as the cache holds them, suffix columns empty
    prefix = jax.tree_util.tree_map(lambda c: c.at[:, hit:].set(0), full["cache"])
    suffix = prefill(ids[:, hit:], prefix, hit)
    np.testing.assert_array_equal(np.asarray(full["logits"][:, hit:]), np.asarray(suffix["logits"]))
    for a, b in zip(jax.tree_util.tree_leaves(full["cache"]),
                    jax.tree_util.tree_leaves(suffix["cache"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("capacity_factor,enabled", [(0.0, True), (1.25, False)])
def test_prefix_cache_gate_is_off_for_capacity_routing_only(capacity_factor, enabled):
    from trlx_tpu.trainer.base import TPUBaseTrainer

    stub = types.SimpleNamespace(
        config=types.SimpleNamespace(engine=types.SimpleNamespace(prefix_cache=True)),
        tcfg=dataclasses.replace(CFG, moe_capacity_factor=capacity_factor),
    )
    assert TPUBaseTrainer._prefix_cache_enabled(stub) is enabled


def test_dropless_refuses_an_expert_axis():
    from jax.sharding import Mesh
    from trlx_tpu.parallel.mesh import set_global_mesh

    devices = np.asarray(jax.devices()[:2])
    if devices.size < 2:
        pytest.skip("needs two devices")
    x = jnp.ones((2, 4, CFG.hidden_size), jnp.float32)
    params = seeded_params(0)["h_0"]["mlp"]
    set_global_mesh(Mesh(devices, ("expert",)))
    try:
        with pytest.raises(ValueError, match="expert"):
            jax.jit(lambda p: MoEMLP(CFG).apply({"params": p}, x))(params)
    finally:
        set_global_mesh(None)


@pytest.mark.parametrize("std", [0.02, 1.0])
def test_embed_init_std_scales_the_token_embedding_alone(std):
    """The stand-in weights of the benchmark's OLMoE configuration: the
    embedding at std 1 so that a random router follows the token, every
    other matrix at the program's 0.02."""
    cfg = dataclasses.replace(CFG, embed_init_std=std)
    params = jax.jit(lambda k: CausalTransformer(cfg).init(k, jnp.zeros((1, 8), jnp.int32))["params"])(
        jax.random.PRNGKey(0))
    assert float(jnp.std(params["wte"]["embedding"])) == pytest.approx(std, rel=0.05)
    assert float(jnp.std(params["lm_head"]["kernel"])) == pytest.approx(0.02, rel=0.05)
    assert float(jnp.std(params["h_0"]["mlp"]["w_up"])) == pytest.approx(0.02, rel=0.05)
