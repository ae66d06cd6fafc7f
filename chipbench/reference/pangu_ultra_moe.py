"""openPangu-Ultra-MoE-718B's forward pass, plainly.

Written from the published ``FreedomIntelligence/openPangu-Ultra-MoE-718B``
``config.json`` (``model_type`` ``pangu_ultra_moe``): a decoder of 61 layers
with latent attention, a norm before AND after each sublayer, three leading
dense layers and 58 layers of 256 routed experts beside one shared expert;
RMSNorm (eps 1e-5, learned scale), no bias anywhere, an untied head. ``x``
is the residual stream.

- Layer: ``a = x + N2(Attn(N1(x)))``, ``y = a + N4(FFN(N3(a)))``: four
  RMSNorms a layer (``sandwich_norm``).
- Attention: ``cq = RMS(x Wqa)`` (1536); ``q = cq Wqb``: 128 heads of
  ``[q_n(128) | q_r(64)]``; ``[ckv(512) | k_r(64)] = x Wkva``, ``c =
  RMS(ckv)``, ONE ``k_r`` for all heads; rotary embedding (theta 25.6e6,
  split-half pairs) on ``q_r`` and ``k_r`` only; ``[k_n(128) | v(128)]`` a
  head ``= c Wkvb``; scores ``(q_n . k_n + q_r . k_r) / sqrt(192)``, causal
  softmax, ``o = sum p v``, output ``concat(o) Wo`` (16384 -> 7680).
- Layers ``0 .. first_k_dense_replace - 1``: SwiGLU ``down(silu(gate n) *
  up n)`` at ``intermediate_size``.
- The other layers: ``s = sigmoid(n Wr)`` over the router's 256; the 8
  largest; ``w = 2.5 * s_top / sum(s_top)`` (``norm_topk_prob``,
  ``routed_scaling_factor``); ``y = sum_e w_e E_e(n) + S(n)``, ``E_e`` and the
  shared expert ``S`` SwiGLU of ``moe_intermediate_size``; no capacity bound.
- Final RMSNorm, untied head.

Plain ``jax.numpy`` in float32 under ``highest`` matmul precision: EXPANDED
attention only (per-head K and V built from the latent; no cache, no
absorption, no kernel), the gate a dense ``[tokens, router width]`` matrix,
every held expert applied to every token, one expert at a time. It walks
the system's own parameter tree one layer at a time and casts that layer up.
The sizes come from ``dims``, the published keys of the configuration file.
A projection that carries a LoRA adapter (``lora_a``, ``lora_b`` beside its
``kernel``: the cell trains adapters) adds ``(alpha / r) x A B``, ``alpha``
from ``dims["lora_alpha"]`` (the configuration file's ``peft_kwargs``).

**One chip's share.** The expert kernels of the tree hold
``dims["n_routed_experts"]`` experts, the slice ``[first, first + held)`` of
the router's width (``first`` is ``dims["moe_first_expert_held"]``, 0 if
absent; the width is the router kernel's). The reference is given the same
share as the program: it routes over the whole width, renormalises over all
eight chosen, and adds only what the held experts give, and the shared
expert whole (every chip of the deployment computes it alike). ``moe_layer``
is that one layer alone, its routed part and its shared part apart, for the
test that the shares add up to the uncut layer with the shared expert
counted once.

Departures from the publication, all of them: (1) left padding gets
positions ``cumsum(mask) - 1``. (2) The next-token-prediction module
(``num_nextn_predict_layers`` 1) is not built: a training auxiliary and a
drafter, which PPO neither reads nor trains. Readings the config does not
settle, as the configuration file's ``assumed`` says, each with its other
reading as a planted fault: sigmoid scoring with no group limit and no
selection bias (the config has no ``scoring_func``, ``n_group``,
``topk_group``; the family's convention beside ``routed_scaling_factor``):
``softmax_router``; split-half rotary pairs, no rope scaling, no extra
softmax scale.

``fault`` plants a known error for the yardstick's control run:
``"softmax_router"`` scores with a softmax over the experts,
``"no_routed_scaling"`` drops the 2.5, ``"no_shared_expert"`` the shared
expert, ``"no_sandwich_norm"`` the two post-sublayer norms,
``"no_latent_norm"`` the RMSNorms on both latents, ``"rope_on_all_dims"``
rotates all 192 dims of q and k (the shared key's 64 rotated with the first
64 frequencies of the 192), ``"per_head_rope_key"`` gives head ``h`` the
shared roped key rolled by ``h`` columns (a key of its own a head),
``"first_layer_sparse"`` reads ``first_k_dense_replace`` as 0: the leading
layers run the first sparse layer's feed-forward (its router, experts and
shared expert; the tree holds no experts of their own for them) in place of
their dense one.
``"fp8_weights"`` is the control for precision, not a fault: every matrix
(the expert kernels too) rounded to ``float8_e4m3fn``, the nearest precision
below the stated bf16.
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = ("softmax_router", "no_routed_scaling", "no_shared_expert", "no_sandwich_norm",
          "no_latent_norm", "rope_on_all_dims", "per_head_rope_key", "first_layer_sparse")
# not a fault of the mathematics but the control for precision
PRECISION_CONTROL = "fp8_weights"
Q_BLOCK = 256  # query rows a block of attention: [rows, heads, 256, T] float32 scores


def _up(tree, fault=None):
    def up(x):
        if fault == PRECISION_CONTROL and x.ndim >= 2:
            x = jnp.asarray(x, F32).astype(jnp.float8_e4m3fn)
        return jnp.asarray(x, F32)

    return jax.tree_util.tree_map(up, tree)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x, positions, theta, dim=None):
    """x [B, T, H, D]: the first ``dim`` columns rotated (all of them where
    None), pairs ``(i, i + dim/2)``."""
    d = x.shape[-1] if dim is None else dim
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions[..., None].astype(F32) * inv_freq  # [B, T, d/2]
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2, rest = x[..., : d // 2], x[..., d // 2 : d], x[..., d:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attention(q, k, v, mask):
    """q, k [B, T, H, Dqk] and v [B, T, H, Dv], a block of query rows at a time."""
    b, t, heads, d = q.shape
    n_blocks = -(-t // Q_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, n_blocks * Q_BLOCK - t), (0, 0), (0, 0)))
    ki = jnp.arange(t)[None, :]

    def block(i):
        rows = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=1)
        qi = (i * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None]
        visible = (ki <= qi)[None, None] & (mask[:, None, None, :] > 0)
        scores = jnp.einsum("bqhd,bkhd->bhqk", rows, k) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(visible, scores, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    out = jax.lax.map(block, jnp.arange(n_blocks))  # [blocks, B, Q_BLOCK, H, Dv]
    return jnp.moveaxis(out, 0, 1).reshape(b, n_blocks * Q_BLOCK, heads, v.shape[-1])[:, :t]


def _proj(p, x, lora_alpha):
    """``x W``, plus the low-rank adapter ``(alpha / r) x A B`` where the
    projection carries one (``model.peft_kwargs``)."""
    y = x @ p["kernel"]
    if "lora_a" in p:
        y = y + (lora_alpha / p["lora_a"].shape[1]) * ((x @ p["lora_a"]) @ p["lora_b"])
    return y


def latent_attention(p, h, mask, positions, *, heads, nope, rope, v_dim, eps, theta, lora_alpha=16.0,
                     fault=None):
    """``Attn(h)`` of one layer's ``attn`` subtree (float32), expanded."""
    b, t, _ = h.shape
    r = p["kv_a_norm"]["scale"].shape[0]
    cq = _proj(p["q_a_proj"], h, lora_alpha)
    kv_a = _proj(p["kv_a_proj"], h, lora_alpha)
    c, k_r = kv_a[..., :r], kv_a[..., r:]
    if fault != "no_latent_norm":
        cq = _rms_norm(cq, p["q_a_norm"]["scale"], eps)
        c = _rms_norm(c, p["kv_a_norm"]["scale"], eps)
    q = _proj(p["q_b_proj"], cq, lora_alpha).reshape(b, t, heads, nope + rope)
    kv = (c @ p["kv_b_proj"]["kernel"]).reshape(b, t, heads, nope + v_dim)
    k_n, v = kv[..., :nope], kv[..., nope:]
    k_r = jnp.broadcast_to(k_r[:, :, None, :], (b, t, heads, rope))
    if fault == "per_head_rope_key":  # a roped key of its own a head
        k_r = jnp.stack([jnp.roll(k_r[:, :, hd], hd, axis=-1) for hd in range(heads)], axis=2)
    if fault == "rope_on_all_dims":
        q = _rotary(q, positions, theta)
        k = _rotary(jnp.concatenate([k_n, k_r], axis=-1), positions, theta)
    else:
        q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], positions, theta)], axis=-1)
        k = jnp.concatenate([k_n, _rotary(k_r, positions, theta)], axis=-1)
    out = _attention(q, k, v, mask)
    return _proj(p["o_proj"], out.reshape(b, t, heads * v_dim), lora_alpha)


def _swiglu(p, n):
    return (jax.nn.silu(n @ p["gate_proj"]["kernel"]) * (n @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def gates(router_logits, top_k, scaling, fault=None):
    """The dense gate matrix ``[..., router width]``: the sigmoid of each
    logit, kept at each token's ``top_k`` largest, those divided by their sum
    and multiplied by ``scaling``, zero elsewhere."""
    if fault == "softmax_router":
        scores = jax.nn.softmax(router_logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(router_logits)
    kth = jnp.sort(scores, axis=-1)[..., -top_k][..., None]
    g = jnp.where(scores >= kth, scores, 0.0)
    g = g / jnp.sum(g, axis=-1, keepdims=True)
    return g if fault == "no_routed_scaling" else g * scaling


def _routed(p, n, g, first):
    """The held experts' part: expert ``first + e`` on every token, plainly."""
    y = jnp.zeros_like(n)
    for e in range(p["w_up"].shape[0]):
        inner = jax.nn.silu(n @ p["w_gate"][e]) * (n @ p["w_up"][e])
        y = y + g[..., first + e : first + e + 1] * (inner @ p["w_down"][e])
    return y


def moe_layer(mlp, n, top_k, scaling, first=0, fault=None):
    """One sparse layer alone, in float32: ``(routed, shared)``, the part of
    ``sum_e w_e E_e(n)`` that the experts held in ``mlp`` (``[first, first +
    held)`` of the router's width) give, and ``S(n)``."""
    with jax.default_matmul_precision("highest"):
        p = _up(mlp)
        n = jnp.asarray(n, F32)
        g = gates(n @ p["router"]["kernel"], top_k, scaling, fault)
        return _routed(p, n, g, first), _swiglu(p["shared_expert"], n)


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "v_dim", "eps", "theta", "top_k", "scaling", "first", "lora_alpha",
    "fault"))
def _layer(layer, x, mask, positions, *, heads, nope, rope, v_dim, eps, theta, top_k, scaling,
           first, lora_alpha=16.0, fault=None):
    with jax.default_matmul_precision("highest"):
        p = _up(layer, fault)
        sandwich = fault != "no_sandwich_norm"
        attn = latent_attention(p["attn"], _rms_norm(x, p["ln_attn"]["scale"], eps), mask, positions,
                                heads=heads, nope=nope, rope=rope, v_dim=v_dim, eps=eps, theta=theta,
                                lora_alpha=lora_alpha, fault=fault)
        if sandwich:
            attn = _rms_norm(attn, p["ln_attn_post"]["scale"], eps)
        a = x + attn
        n = _rms_norm(a, p["ln_mlp"]["scale"], eps)
        mlp = p["mlp"]
        if "router" not in mlp:  # a leading dense layer
            y = _swiglu(mlp, n)
        else:
            g = gates(n @ mlp["router"]["kernel"], top_k, scaling, fault)
            y = _routed(mlp, n, g, first)
            if fault != "no_shared_expert":
                y = y + _swiglu(mlp["shared_expert"], n)
        if sandwich:
            y = _rms_norm(y, p["ln_mlp_post"]["scale"], eps)
        return a + y


@functools.partial(jax.jit, static_argnames=("eps", "fault"))
def _head(ln_f, lm_head, x, *, eps, fault=None):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, jnp.asarray(ln_f["scale"], F32), eps)
        return h @ _up(lm_head, fault)["kernel"]


def hidden(params, dims, input_ids, attention_mask, fault=None):
    """The residual stream ``[B, T, hidden]`` after the last layer, in float32."""
    mask = jnp.asarray(attention_mask, jnp.int32)
    positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    x = _up(params["wte"], fault)["embedding"][jnp.asarray(input_ids)]
    dense = int(dims["first_k_dense_replace"])
    for l in range(int(dims["num_hidden_layers"])):
        layer = params[f"h_{l}"]
        if fault == "first_layer_sparse" and l < dense:
            layer = dict(layer, mlp=params[f"h_{dense}"]["mlp"])
        x = _layer(
            layer, x, mask, positions,
            heads=int(dims["num_attention_heads"]),
            nope=int(dims["qk_nope_head_dim"]),
            rope=int(dims["qk_rope_head_dim"]),
            v_dim=int(dims["v_head_dim"]),
            eps=float(dims["rms_norm_eps"]),
            theta=float(dims["rope_theta"]),
            top_k=int(dims["num_experts_per_tok"]),
            scaling=float(dims["routed_scaling_factor"]),
            first=int(dims.get("moe_first_expert_held", 0)),
            lora_alpha=float(dims.get("lora_alpha", 16.0)),
            fault=fault,
        )
    return x


def logits(params, dims, input_ids, attention_mask, span, fault=None):
    """Float32 logits ``[B, span[1] - span[0], vocab]`` of the backbone tree
    ``params`` on ``input_ids`` [B, T] with ``attention_mask`` [B, T]."""
    x = hidden(params, dims, input_ids, attention_mask, fault)
    return _head(params["ln_f"], params["lm_head"], x[:, span[0] : span[1]],
                 eps=float(dims["rms_norm_eps"]), fault=fault)
