"""OLMoE-1B-7B's forward pass, plainly.

Written from the published description (Muennighoff et al. 2024, "OLMoE:
Open Mixture-of-Experts Language Models", arXiv:2409.02060, and the
``allenai/OLMoE-1B-7B-0125-Instruct`` ``config.json``): a pre-norm decoder;
RMSNorm; q, k, v projections without bias; RMSNorm with a learned scale over
the whole projected width of q and of k (all heads together), before the
split into heads and before rotary (QK-norm); 16 heads of 128, as many
key/value heads; rotary embeddings over the whole head (split-half pairing,
theta 10000); causal attention; every feed-forward is a sparse layer of 64
SwiGLU experts of width 1024: router ``x W_r`` without bias, softmax over
the 64, the eight largest probabilities used as they are (``norm_topk_prob``
false: not renormalised), output ``sum_e p_e down_e(silu(gate_e x) * up_e
x)``; no capacity bound, no shared expert; final RMSNorm; an untied head.

Plain ``jax.numpy`` in float32 under ``highest`` matmul precision: no
kernel, no cache, no sort, no grouped matmul. The gate is a dense
``[tokens, experts]`` matrix, zero outside each token's top-k, and every
expert is applied to every token, one expert at a time. It walks the
system's own parameter tree one layer at a time and casts that layer up, so
it fits beside a trainer. The sizes come from ``dims``, the published keys
of the configuration file.

Departure from the publication: none in the mathematics. Left padding gets
positions ``cumsum(mask) - 1`` (what a Hugging Face user passes as
``position_ids`` for a left-padded batch).

``fault`` plants a known error for the yardstick's control run:
``"no_rotary"`` skips the rotary embedding, ``"strict_causal"`` hides each
position from itself, ``"no_qk_norm"`` skips the two norms on q and k,
``"renormalized_topk"`` divides the eight probabilities by their sum,
``"top7"`` drops each token's eighth expert (what a capacity overflow does).
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = ("no_rotary", "strict_causal", "no_qk_norm", "renormalized_topk", "top7")


def _up(tree):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, F32), tree)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x, positions, theta):
    """x [B, T, H, D]; pairs are (i, i + D/2)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions[..., None].astype(F32) * inv_freq  # [B, T, D/2]
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def gates(h, router, top_k, fault=None):
    """The dense gate matrix ``[..., experts]`` of inputs ``h [..., hidden]``:
    softmax of the router's logits, kept at each token's ``top_k`` largest
    entries and zero elsewhere."""
    probs = jax.nn.softmax(h @ router, axis=-1)
    keep = top_k - 1 if fault == "top7" else top_k
    kth = jnp.sort(probs, axis=-1)[..., -keep][..., None]
    g = jnp.where(probs >= kth, probs, 0.0)
    if fault == "renormalized_topk":
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return g


def _sparse_mlp(p, h, top_k, fault):
    g = gates(h, p["router"]["kernel"], top_k, fault)  # [B, T, E]
    y = jnp.zeros_like(h)
    for e in range(g.shape[-1]):  # every expert on every token, plainly
        inner = jax.nn.silu(h @ p["w_gate"][e]) * (h @ p["w_up"][e])
        y = y + g[..., e : e + 1] * (inner @ p["w_down"][e])
    return y


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta", "top_k", "fault"))
def _layer(layer, x, mask, positions, *, heads, kv_heads, eps, theta, top_k, fault=None):
    with jax.default_matmul_precision("highest"):
        p = _up(layer)
        b, t, e = x.shape
        d = e // heads
        h = _rms_norm(x, p["ln_attn"]["scale"], eps)
        q = h @ p["attn"]["q_proj"]["kernel"]
        k = h @ p["attn"]["k_proj"]["kernel"]
        if fault != "no_qk_norm":  # over the whole width, before the heads
            q = _rms_norm(q, p["attn"]["q_norm"]["scale"], eps)
            k = _rms_norm(k, p["attn"]["k_norm"]["scale"], eps)
        q = q.reshape(b, t, heads, d)
        k = k.reshape(b, t, kv_heads, d)
        v = (h @ p["attn"]["v_proj"]["kernel"]).reshape(b, t, kv_heads, d)
        if fault != "no_rotary":
            q, k = _rotary(q, positions, theta), _rotary(k, positions, theta)
        rep = heads // kv_heads  # 1 as published
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
        qi, ki = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        visible = (ki < qi) if fault == "strict_causal" else (ki <= qi)
        visible = visible[None, None] & (mask[:, None, None, :] > 0)
        scores = jnp.where(visible, scores, -1e30)
        attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
        x = x + attn.reshape(b, t, e) @ p["attn"]["o_proj"]["kernel"]
        h = _rms_norm(x, p["ln_mlp"]["scale"], eps)
        return x + _sparse_mlp(p["mlp"], h, top_k, fault)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(ln_f, lm_head, x, *, eps):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, jnp.asarray(ln_f["scale"], F32), eps)
        return h @ jnp.asarray(lm_head["kernel"], F32)


def hidden(params, dims, input_ids, attention_mask, fault=None):
    """The residual stream ``[B, T, hidden]`` after the last layer, in
    float32."""
    mask = jnp.asarray(attention_mask, jnp.int32)
    positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    x = jnp.asarray(params["wte"]["embedding"], F32)[jnp.asarray(input_ids)]
    for i in range(int(dims["num_hidden_layers"])):
        x = _layer(
            params[f"h_{i}"], x, mask, positions,
            heads=int(dims["num_attention_heads"]),
            kv_heads=int(dims["num_key_value_heads"]),
            eps=float(dims["rms_norm_eps"]),
            theta=float(dims["rope_theta"]),
            top_k=int(dims["num_experts_per_tok"]),
            fault=fault,
        )
    return x


def logits(params, dims, input_ids, attention_mask, span, fault=None):
    """Float32 logits ``[B, span[1] - span[0], vocab]`` of the backbone tree
    ``params`` on ``input_ids`` [B, T] with ``attention_mask`` [B, T]."""
    x = hidden(params, dims, input_ids, attention_mask, fault)
    return _head(params["ln_f"], params["lm_head"], x[:, span[0] : span[1]],
                 eps=float(dims["rms_norm_eps"]))
