"""Mistral-7B's forward pass, plainly.

Written from the published description (Jiang et al. 2023, "Mistral 7B",
arXiv:2310.06825, and the ``mistralai/Mistral-7B-v0.1`` ``config.json``): a
pre-norm decoder; RMSNorm; grouped-query attention (32 query heads share 8
key/value heads, 4 to 1, head size 128); rotary embeddings over the whole
head (split-half pairing, theta 10000); a causal sliding window of 4096; a
SwiGLU feed-forward of 14336; an untied output head; no bias anywhere.

Plain ``jax.numpy`` in float32 under ``highest`` matmul precision: no kernel,
no cache, no batching tricks. It walks the system's own parameter tree one
layer at a time and casts that layer up, so it fits beside a trainer. The
sizes come from ``dims``, the published keys of the configuration file.

Departure from the publication: none in the mathematics. Left padding gets
positions ``cumsum(mask) - 1`` (what a Hugging Face user passes as
``position_ids`` for a left-padded batch).

``fault`` plants a known error for the yardstick's control run:
``"no_rotary"`` skips the rotary embedding, ``"strict_causal"`` hides each
position from itself (an off-by-one in the causal mask).
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


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


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta", "window", "rotary", "strict"))
def _layer(layer, x, mask, positions, *, heads, kv_heads, eps, theta, window, rotary, strict=False):
    with jax.default_matmul_precision("highest"):
        p = _up(layer)
        b, t, e = x.shape
        d = e // heads
        h = _rms_norm(x, p["ln_attn"]["scale"], eps)
        q = (h @ p["attn"]["q_proj"]["kernel"]).reshape(b, t, heads, d)
        k = (h @ p["attn"]["k_proj"]["kernel"]).reshape(b, t, kv_heads, d)
        v = (h @ p["attn"]["v_proj"]["kernel"]).reshape(b, t, kv_heads, d)
        if rotary:
            q, k = _rotary(q, positions, theta), _rotary(k, positions, theta)
        rep = heads // kv_heads  # query head i reads key/value head i // rep
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
        qi, ki = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        visible = ((ki < qi) if strict else (ki <= qi)) & (qi - ki < window)
        visible = visible[None, None] & (mask[:, None, None, :] > 0)
        scores = jnp.where(visible, scores, -1e30)
        attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
        x = x + attn.reshape(b, t, e) @ p["attn"]["o_proj"]["kernel"]
        h = _rms_norm(x, p["ln_mlp"]["scale"], eps)
        gate = jax.nn.silu(h @ p["mlp"]["gate_proj"]["kernel"])
        up = h @ p["mlp"]["up_proj"]["kernel"]
        return x + (gate * up) @ p["mlp"]["down_proj"]["kernel"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(ln_f, lm_head, x, *, eps):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, jnp.asarray(ln_f["scale"], F32), eps)
        return h @ jnp.asarray(lm_head["kernel"], F32)


def logits(params, dims, input_ids, attention_mask, span, fault=None):
    """Float32 logits ``[B, span[1] - span[0], vocab]`` of the backbone tree
    ``params`` on ``input_ids`` [B, T] with ``attention_mask`` [B, T]."""
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
            window=int(dims["sliding_window"]),
            rotary=fault != "no_rotary",
            strict=fault == "strict_causal",
        )
    return _head(params["ln_f"], params["lm_head"], x[:, span[0] : span[1]],
                 eps=float(dims["rms_norm_eps"]))
