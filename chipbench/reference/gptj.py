"""GPT-J-6B's forward pass, plainly.

Written from the published description (Wang & Komatsuzaki 2021,
"GPT-J-6B", the mesh-transformer-jax repository, and the
``EleutherAI/gpt-j-6b`` ``config.json``): a decoder whose block computes
attention and feed-forward in parallel from ONE LayerNorm and adds both to
the residual; 16 heads of 256 with no bias on q, k, v or the output
projection; rotary embeddings on the first 64 dims of each head, pairing
neighbours (2i, 2i+1), theta 10000; a GELU (tanh form) feed-forward of
``4 * n_embd`` with biases; a final LayerNorm; an untied output head WITH a
bias.

Plain ``jax.numpy`` in float32 under ``highest`` matmul precision: no kernel,
no cache. It walks the system's own parameter tree one layer at a time and
casts that layer up. The sizes come from ``dims``, the published keys of the
configuration file.

Departure from the publication: none in the mathematics. Left padding gets
positions ``cumsum(mask) - 1``.

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


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def _rotary(x, positions, rotary_dim, theta):
    """x [B, T, H, D]; the first ``rotary_dim`` dims rotate, pairs (2i, 2i+1)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=F32) / rotary_dim))
    ang = positions[..., None].astype(F32) * inv_freq
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    x1, x2 = rot[..., 0::2], rot[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.reshape(rot.shape), rest], axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "rotary_dim", "theta", "rotary", "strict"))
def _layer(layer, x, mask, positions, *, heads, eps, rotary_dim, theta, rotary, strict=False):
    with jax.default_matmul_precision("highest"):
        p = _up(layer)
        b, t, e = x.shape
        d = e // heads
        h = _layer_norm(x, p["ln_attn"]["scale"], p["ln_attn"]["bias"], eps)
        q = (h @ p["attn"]["q_proj"]["kernel"]).reshape(b, t, heads, d)
        k = (h @ p["attn"]["k_proj"]["kernel"]).reshape(b, t, heads, d)
        v = (h @ p["attn"]["v_proj"]["kernel"]).reshape(b, t, heads, d)
        if rotary:
            q = _rotary(q, positions, rotary_dim, theta)
            k = _rotary(k, positions, rotary_dim, theta)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
        qi, ki = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        visible = ((ki < qi) if strict else (ki <= qi))[None, None] & (mask[:, None, None, :] > 0)
        scores = jnp.where(visible, scores, -1e30)
        attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
        attn_out = attn.reshape(b, t, e) @ p["attn"]["o_proj"]["kernel"]
        up = _gelu_new(h @ p["mlp"]["up_proj"]["kernel"] + p["mlp"]["up_proj"]["bias"])
        mlp_out = up @ p["mlp"]["down_proj"]["kernel"] + p["mlp"]["down_proj"]["bias"]
        return x + attn_out + mlp_out


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(ln_f, lm_head, x, *, eps):
    with jax.default_matmul_precision("highest"):
        ln_f, lm_head = _up(ln_f), _up(lm_head)
        h = _layer_norm(x, ln_f["scale"], ln_f["bias"], eps)
        return h @ lm_head["kernel"] + lm_head["bias"]


def logits(params, dims, input_ids, attention_mask, span, fault=None):
    """Float32 logits ``[B, span[1] - span[0], vocab]`` of the backbone tree
    ``params`` on ``input_ids`` [B, T] with ``attention_mask`` [B, T]."""
    mask = jnp.asarray(attention_mask, jnp.int32)
    positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    x = jnp.asarray(params["wte"]["embedding"], F32)[jnp.asarray(input_ids)]
    for i in range(int(dims["n_layer"])):
        x = _layer(
            params[f"h_{i}"], x, mask, positions,
            heads=int(dims["n_head"]),
            eps=float(dims["layer_norm_epsilon"]),
            rotary_dim=int(dims["rotary_dim"]),
            theta=10000.0,
            rotary=fault != "no_rotary",
            strict=fault == "strict_causal",
        )
    return _head(params["ln_f"], params["lm_head"], x[:, span[0] : span[1]],
                 eps=float(dims["layer_norm_epsilon"]))
