"""LFM2-8B-A1B's forward pass, plainly.

Written from the catalog row's ``config`` (``LiquidAI/LFM2-8B-A1B``,
``model_type: lfm2_moe``) and the LFM2 technical description the row's
``described_as`` summarises (a gated short convolution as a layer's sequence
mixer, 18 layers in 24; GQA attention with a per-head QK-norm on the others;
two leading dense layers; sigmoid-routed experts under a selection bias).
``x`` is the residual stream, ``u`` a block's normed input, ``d`` the hidden
size. RMSNorm everywhere (``x * rsqrt(mean(x^2) + norm_eps) * w``), no bias
anywhere, the head tied to the token embedding.

- Model: ``x_0 = E[id]``; block ``l``: ``h = x + Mixer_l(RMSNorm_op(x))``,
  ``y = h + FFN_l(RMSNorm_ffn(h))``; ``logits = RMSNorm_f(x_L) E^T``.
  ``layer_types[l]`` says which mixer, ``l < num_dense_layers`` which FFN.
- ``conv`` mixer on ``u``: ``[B_t | C_t | z_t] = W_in u_t`` (``d -> 3d``, split
  in that order); ``g_t = B_t * z_t``; ``c_t = sum_{k=0..K-1} w_k * g_{t - K +
  1 + k}`` a channel (``K = conv_L_cache`` taps, the oldest first, zeros left
  of the row's first real token); ``o_t = W_out (C_t * c_t)``. No activation.
- ``full_attention`` mixer on ``u``: ``q = W_q u`` as ``num_attention_heads``
  heads of ``D = d / heads``, ``k = W_k u`` and ``v = W_v u`` as
  ``num_key_value_heads`` heads of ``D``; ``q <- RMSNorm_D(q)``, ``k <-
  RMSNorm_D(k)`` a head, one learned scale of ``D`` each that the heads share;
  rotary embedding on all ``D`` dims, split-half pairs ``(i, i + D/2)``,
  ``rope_theta``; ``softmax(q k^T / sqrt(D))`` causal, a KV head serving
  ``heads / kv heads`` query heads; ``W_o``.
- Dense FFN: ``W_2 (silu(W_1 n) * W_3 n)`` of ``intermediate_size``.
- Expert FFN on ``n``: ``s = sigmoid(W_r n)`` over the router's width;
  ``chosen = top_k(s + b)``, ``b`` the expert bias (it selects and weighs
  nothing); ``gate_e = s_e / (sum_chosen s + 1e-6) * routed_scaling_factor``;
  ``sum_e gate_e W_2e (silu(W_1e n) * W_3e n)`` of ``moe_intermediate_size``;
  no shared expert.

Plain ``jax.numpy`` in float32 under ``highest`` matmul precision, one row at
a time: no cache, no kernel, no batching; the gate a dense ``[tokens, router
width]`` matrix, every held expert applied to every token. It walks the
system's own parameter tree one layer at a time and casts that layer up.

**One chip's share.** The expert kernels hold ``dims["num_experts"]`` experts,
the slice ``[first, first + held)`` of the router's width (``first`` is
``dims["moe_first_expert_held"]``, 0 where absent); the reference routes over
the whole width, renormalises over all the chosen and adds only what the held
experts give. ``moe_layer`` is that one layer alone, for the test that the
shares add up to the uncut layer. The vocabulary is whatever slice the
embedding holds.

What the row's ``config`` does not settle is listed in
``chipbench/configs/lfm2-8b-a1b-l10e8.json`` under ``assumed``, each with its
other reading; the other readings that can be told apart are planted faults.

``fault`` plants a known error for the yardstick's control run: ``"no_conv"``
(taps 0, .., 0, 1: ``c = g``), ``"no_input_gate"`` (``g = z``),
``"no_output_gate"`` (``o = W_out c``), ``"gate_c_first"`` (``in_proj`` split
as ``C | B | z``), ``"conv_silu"`` (``silu`` after the conv, as Mamba's),
``"no_qk_norm"``, ``"softmax_router"``, ``"no_selection_bias"``,
``"no_renormalize"`` (``gate_e = s_e``), ``"untied_head"`` (a head of its own,
drawn at the program's 0.02 from a fixed key). The control for precision, not
a fault: ``"fp8_weights"`` (every matrix rounded to ``float8_e4m3fn``, the
nearest precision below the stated bfloat16).
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = ("no_conv", "no_input_gate", "no_output_gate", "gate_c_first", "conv_silu", "no_qk_norm",
          "softmax_router", "no_selection_bias", "no_renormalize", "untied_head")
PRECISION_CONTROLS = ("fp8_weights",)
RENORM_EPS = 1e-6


def _up(tree, fault=None):
    def up(x):
        if fault == "fp8_weights" and x.ndim >= 2:
            x = jnp.asarray(x, F32).astype(jnp.float8_e4m3fn)
        return jnp.asarray(x, F32)

    return jax.tree_util.tree_map(up, tree)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x, positions, theta):
    """x [T, H, D]: all ``D`` columns rotated, pairs ``(i, i + D/2)``."""
    dim = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    ang = positions[:, None].astype(F32) * inv_freq
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def short_conv(p, u, mask, fault=None):
    """The ``conv`` mixer on ONE row ``u [T, d]`` from one layer's ``attn``
    subtree (float32); ``mask [T]`` 1 on real slots: a padded slot feeds
    nothing into the window."""
    T = u.shape[0]
    first, second, z = jnp.split(u @ p["in_proj"]["kernel"], 3, axis=-1)
    b, c = (second, first) if fault == "gate_c_first" else (first, second)
    g = (z if fault == "no_input_gate" else b * z) * mask.astype(F32)[:, None]
    w = p["conv_weight"]
    K = w.shape[0]
    if fault == "no_conv":
        w = jnp.zeros_like(w).at[K - 1].set(1.0)
    seen = jnp.concatenate([jnp.zeros((K - 1, g.shape[1]), F32), g], axis=0)
    conv = sum(seen[k : k + T] * w[k] for k in range(K))
    if fault == "conv_silu":
        conv = jax.nn.silu(conv)
    return (conv if fault == "no_output_gate" else c * conv) @ p["out_proj"]["kernel"]


def attention(p, u, mask, positions, *, heads, kv_heads, eps, theta, fault=None):
    """The ``full_attention`` mixer on ONE row from one layer's ``attn`` subtree."""
    T = u.shape[0]
    q = (u @ p["q_proj"]["kernel"]).reshape(T, heads, -1)
    k = (u @ p["k_proj"]["kernel"]).reshape(T, kv_heads, -1)
    v = (u @ p["v_proj"]["kernel"]).reshape(T, kv_heads, -1)
    D = q.shape[-1]
    if fault != "no_qk_norm":
        q, k = _rms_norm(q, p["q_norm"]["scale"], eps), _rms_norm(k, p["k_norm"]["scale"], eps)
    q, k = _rotary(q, positions, theta), _rotary(k, positions, theta)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)  # KV head j serves query heads [j group, (j + 1) group)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(D)
    at = jnp.arange(T)
    visible = (at[None, :] <= at[:, None]) & (mask[None, :] > 0)
    probs = jax.nn.softmax(jnp.where(visible[None], scores, -1e30), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(T, heads * D)
    return out @ p["o_proj"]["kernel"]


def _swiglu(p, n):
    return (jax.nn.silu(n @ p["gate_proj"]["kernel"]) * (n @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def gates(router_logits, bias, top_k, scaling, fault=None):
    """The dense gate matrix ``[..., router width]``: the sigmoid of each
    logit, kept at each token's ``top_k`` largest of ``score + bias``, those
    SCORES over ``their sum + 1e-6``, times ``scaling``, zero elsewhere."""
    scores = jax.nn.softmax(router_logits, axis=-1) if fault == "softmax_router" else jax.nn.sigmoid(router_logits)
    choosing = scores if fault == "no_selection_bias" else scores + bias
    kth = jnp.sort(choosing, axis=-1)[..., -top_k][..., None]
    g = jnp.where(choosing >= kth, scores, 0.0)
    if fault != "no_renormalize":
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + RENORM_EPS)
    return g * scaling


def _routed(p, n, g, first):
    """The held experts' part: expert ``first + e`` on every token, plainly."""
    y = jnp.zeros_like(n)
    for e in range(p["w_up"].shape[0]):
        inner = jax.nn.silu(n @ p["w_gate"][e]) * (n @ p["w_up"][e])
        y = y + g[..., first + e : first + e + 1] * (inner @ p["w_down"][e])
    return y


def moe_layer(mlp, n, top_k, scaling, first=0, fault=None):
    """One expert layer alone, in float32: the part of ``sum_e gate_e E_e(n)``
    that the experts held in ``mlp`` (``[first, first + held)`` of the
    router's width) give."""
    with jax.default_matmul_precision("highest"):
        p = _up(mlp)
        n = jnp.asarray(n, F32)
        return _routed(p, n, gates(n @ p["router"]["kernel"], p["router_bias"], top_k, scaling, fault), first)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta", "top_k", "scaling", "first", "fault"))
def _layer(layer, x, mask, positions, *, heads, kv_heads, eps, theta, top_k, scaling, first, fault=None):
    """One row ``x [T, d]`` through one layer."""
    with jax.default_matmul_precision("highest"):
        p = _up(layer, fault)
        u = _rms_norm(x, p["ln_attn"]["scale"], eps)
        if "conv_weight" in p["attn"]:
            mixed = short_conv(p["attn"], u, mask, fault)
        else:
            mixed = attention(p["attn"], u, mask, positions, heads=heads, kv_heads=kv_heads, eps=eps, theta=theta, fault=fault)
        h = x + mixed
        n = _rms_norm(h, p["ln_mlp"]["scale"], eps)
        mlp = p["mlp"]
        if "router" not in mlp:  # a leading dense layer
            return h + _swiglu(mlp, n)
        g = gates(n @ mlp["router"]["kernel"], mlp["router_bias"], top_k, scaling, fault)
        return h + _routed(mlp, n, g, first)


@functools.partial(jax.jit, static_argnames=("eps", "fault"))
def _head(ln_f, wte, x, *, eps, fault=None):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, jnp.asarray(ln_f["scale"], F32), eps)
        embedding = _up(wte, fault)["embedding"]
        if fault == "untied_head":
            embedding = 0.02 * jax.random.normal(jax.random.PRNGKey(0), embedding.shape, F32)
        return h @ embedding.T


def hidden(params, dims, input_ids, attention_mask, fault=None):
    """The residual stream ``[B, T, d]`` after the last layer, in float32, one
    row after another."""
    mask = jnp.asarray(attention_mask, jnp.int32)
    positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    embedding = _up(params["wte"], fault)["embedding"]
    statics = dict(
        heads=int(dims["num_attention_heads"]),
        kv_heads=int(dims["num_key_value_heads"]),
        eps=float(dims["norm_eps"]),
        theta=float(dims["rope_theta"]),
        top_k=int(dims["num_experts_per_tok"]),
        scaling=float(dims["routed_scaling_factor"]),
        first=int(dims.get("moe_first_expert_held", 0)),
        fault=fault,
    )
    depth = int(dims["num_hidden_layers"])
    for l in range(depth):
        layer = params[f"h_{l}"]
        runs = "conv" if "conv_weight" in layer["attn"] else "full_attention"
        if dims["layer_types"][l] != runs:
            raise ValueError(f"layer {l}: layer_types says {dims['layer_types'][l]!r}, the tree runs {runs!r}")
        if ("router" not in layer["mlp"]) != (l < int(dims["num_dense_layers"])):
            raise ValueError(f"layer {l}: num_dense_layers {dims['num_dense_layers']} and the tree's feed-forward kind disagree")
    rows = []
    for b in range(mask.shape[0]):
        x = embedding[jnp.asarray(input_ids)[b]]
        for l in range(depth):
            x = _layer(params[f"h_{l}"], x, mask[b], positions[b], **statics)
        rows.append(x)
    return jnp.stack(rows)


def logits(params, dims, input_ids, attention_mask, span, fault=None):
    """Float32 logits ``[B, span[1] - span[0], vocab]`` of the backbone tree
    ``params`` on ``input_ids`` [B, T] with ``attention_mask`` [B, T]."""
    x = hidden(params, dims, input_ids, attention_mask, fault)
    return _head(params["ln_f"], params["wte"], x[:, span[0] : span[1]], eps=float(dims["norm_eps"]), fault=fault)
