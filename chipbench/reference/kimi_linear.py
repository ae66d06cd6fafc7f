"""Kimi-Linear-48B-A3B's forward pass, plainly.

Written from the catalog row's ``config`` (``moonshotai/Kimi-Linear-48B-A3B-Instruct``,
``model_type: kimi_linear``), the Kimi Linear report (arXiv:2510.26692: Kimi
Delta Attention, KDA), the gated delta net it refines (Yang et al.,
arXiv:2412.06464) and the DeepSeek-V3 style router the row's keys name. ``x``
is the residual stream, ``u = N1(x)`` a layer's normed input. RMSNorm eps
``rms_norm_eps`` with a learned scale, no bias anywhere, an untied head.

- Model: ``x_0 = Emb(id)``; block ``a = x + Mixer_l(N1(x))``, ``y = a +
  FFN_l(N2(a))``; ``logits = W_head N_f(x_L)``. Layers count from 1 in
  ``linear_attn_config``: ``kda_layers`` run KDA, ``full_attn_layers`` latent
  attention; layer 1 (``first_k_dense_replace`` 1) has a dense SwiGLU
  ``Wd (silu(Wg n) * Wu n)`` of ``intermediate_size``, the others experts.
- KDA on ``u``, ``H = num_heads`` heads of ``d = head_dim``, the recurrence
  in float32: ``q~, k~, v~ = Wq u, Wk u, Wv u``; each passes its own causal
  depthwise conv of width ``short_conv_kernel_size`` (no bias: ``y_t = sum_j
  w_j x_{t - K + 1 + j}``) and ``silu``; ``q_t = l2norm(q~_t) / sqrt(d)``,
  ``k_t = l2norm(k~_t)`` a head (``x / sqrt(sum x^2 + 1e-6)``), ``v_t =
  v~_t``; log decay a CHANNEL ``g_t = -exp(A_log_h) softplus(Wf2 (Wf1 u_t) +
  dt_bias)``; ``beta_t = sigmoid(Wb u_t)`` a head. Per head ``S [d, d]`` (key
  channels by value channels), ``S_0 = 0``::

      S'_t = Diag(exp(g_t)) S_{t-1}
      S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
      o_t  = S_t^T q_t

  output ``Wo concat_h(RMSNorm_d(o_{h,t}) * sigmoid(Wg2 (Wg1 u_t))_h)``, the
  norm over each head's own ``d`` channels with ONE learned scale of ``d``.
  A padded slot feeds nothing into the conv window or the state and does not
  decay it. The recurrence runs token by token (``lax.scan``), never in
  chunks.
- Latent attention on ``u``: ``q = Wq u`` as heads of ``[q_n | q_s]``
  (``qk_nope_head_dim`` + ``qk_rope_head_dim``); ``[ckv | k_s] = Wkva u``,
  ``c = RMSNorm(ckv)``; ``[k_n | v] = Wkvb c`` a head; ONE ``k_s`` for all
  heads, NO rotary embedding on ``q_s`` or ``k_s`` (``mla_use_nope``), no norm
  on ``k_s``; scores ``(q_n . k_n + q_s . k_s) / sqrt(dn + ds)``, causal
  softmax, ``Wo concat_h(sum p v)``.
- Expert layer on ``n = N2(a)``: ``s = sigmoid(Wr n)`` over the router's
  width; the ``num_experts_per_token`` largest of ``s + b`` are chosen (``b``
  the selection bias: it chooses and weighs nothing; ``num_expert_group`` 1,
  ``topk_group`` 1: no group limit); weights ``s`` over the sum of the chosen,
  times ``routed_scaling_factor``; ``sum_e w_e Expert_e(n) + Shared(n)``,
  every expert and the shared one a SwiGLU of ``moe_intermediate_size``.

Plain ``jax.numpy`` in float32 under ``highest`` matmul precision, one row at
a time: no chunks, no cache, no kernel, no absorbed form; latent attention a
block of ``QUERY_BLOCK`` queries at a time so that 4096-slot rows fit beside
what a trainer holds (a block's scores are ``[32, 256, T]`` float32); the
gate a dense ``[tokens, router width]`` matrix, every held expert applied to
every token. It walks the system's own parameter tree one layer at a time
and casts that layer up. A projection that carries a low-rank adapter adds
``(lora_alpha / r) x A B`` (``dims["lora_alpha"]``).

**One chip's share.** As ``glm_moe_dsa.py``: the expert kernels hold
``dims["num_experts"]`` experts, the slice ``[first, first + held)`` of the
router's width; the reference routes over the whole width, renormalises over
all the chosen, adds only what the held experts give, and the shared expert
whole. ``moe_layer`` is that one layer alone, routed part and shared part
apart, for the test that the shares add up to the uncut layer.

What the row's ``config`` does not settle is listed in
``chipbench/configs/kimi-linear-48b-a3b-l8e32.json`` under ``assumed``, each
with its other reading; the other readings are planted faults here.

``fault`` plants a known error for the yardstick's control run:
``"scalar_decay"`` (a head's decay the MEAN of its channels' log decays: the
gated delta net's scalar gate, what tells KDA from its parent),
``"correct_before_decay"`` (the delta correction against the UNDECAYED state:
``S_t = Diag(exp(g_t)) S_{t-1} + beta_t k_t (v_t - S_{t-1}^T k_t)^T``),
``"no_l2norm"`` (q and k as the convs leave them), ``"no_conv"`` (q~, k~, v~
straight into ``silu``), ``"no_v_activation"`` (no ``silu`` on v),
``"no_q_scale"`` (the ``1 / sqrt(d)`` dropped), ``"silu_gate"`` (the output
gate a ``silu``), ``"mla_rope"`` (rotary embedding, ``rope_theta``, split-half
pairs, on ``q_s`` and ``k_s``), ``"no_selection_bias"``, ``"softmax_router"``,
``"no_routed_scaling"``, ``"no_shared_expert"``. Controls for precision, not
faults: ``"bf16_state"`` (``S`` rounded to bfloat16 after every token, the
nearest precision below the state's stated float32) and ``"fp8_weights"``
(every matrix rounded to ``float8_e4m3fn``, the nearest below the stated
bfloat16).

``kda_states`` gives a KDA layer's ``S`` and the conv window's rows after
chosen slots from given layer inputs, by the same recurrence: what a
sampler's cache has to hold (``chipbench/kda_state_check.py``, where
``bf16_state`` is the control that has to read not correct).
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = ("scalar_decay", "correct_before_decay", "no_l2norm", "no_conv", "no_v_activation", "no_q_scale",
          "silu_gate", "mla_rope", "no_selection_bias", "softmax_router", "no_routed_scaling", "no_shared_expert")
# not faults of the mathematics but the controls for precision
PRECISION_CONTROLS = ("bf16_state", "fp8_weights")
QUERY_BLOCK = 256  # query rows a block of latent attention; a shorter row is one block of its own length
L2_EPS = 1e-6


def _up(tree, fault=None):
    def up(x):
        if fault == "fp8_weights" and x.ndim >= 2:
            x = jnp.asarray(x, F32).astype(jnp.float8_e4m3fn)
        return jnp.asarray(x, F32)

    return jax.tree_util.tree_map(up, tree)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _rotary(x, positions, theta, dim):
    """x [T, H, D]: the first ``dim`` columns rotated, pairs ``(i, i + dim/2)``."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    ang = positions[:, None].astype(F32) * inv_freq
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2, rest = x[..., : dim // 2], x[..., dim // 2 : dim], x[..., dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _proj(p, x, lora_alpha):
    """``x W``, plus the low-rank adapter ``(alpha / r) x A B`` where the
    projection carries one (``model.peft_kwargs``)."""
    y = x @ p["kernel"]
    if "lora_a" in p:
        y = y + (lora_alpha / p["lora_a"].shape[1]) * ((x @ p["lora_a"]) @ p["lora_b"])
    return y


def _short_conv(x, w):
    """``y_t = sum_j w[j] x_{t - K + 1 + j}`` a channel, zeros in front: ``x
    [T, C]``, ``w [K, C]`` (taps oldest first)."""
    K, T = w.shape[0], x.shape[0]
    seen = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], axis=0)
    return sum(seen[j : j + T] * w[j] for j in range(K))


def delta_rule(q, k, v, g, beta, fault=None, S0=None):
    """The recurrence itself, token by token: ``q, k, g [T, H, d]``, ``v [T,
    H, dv]``, ``beta [T, H]`` -> ``(o [T, H, dv], S [H, d, dv])`` from ``S0``
    (zero where none is given)."""

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        decayed = jnp.exp(g_t)[..., None] * S
        against = S if fault == "correct_before_decay" else decayed
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", against, k_t))
        S = decayed + k_t[..., None] * u[:, None, :]
        if fault == "bf16_state":  # (a convert to bfloat16 and back is elided on the chip: xla_allow_excess_precision)
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    if S0 is None:
        S0 = jnp.zeros((k.shape[1], k.shape[2], v.shape[2]), F32)
    S, o = jax.lax.scan(token, S0, (q, k, v, g, beta))
    return o, S


def _kda_operands(p, u, mask, *, heads, dim, lora_alpha, fault):
    """What the recurrence of ONE row is given: ``(x, q, k, v, g, beta)``, ``x
    [T, 3 heads dim]`` the rows of ``[q~ | k~ | v~]`` in front of the convs,
    ``g`` and ``beta`` zero on a padded slot; ``u`` already zero there."""
    t = u.shape[0]
    real = mask.astype(F32)[:, None]
    x = jnp.concatenate([_proj(p[name], u, lora_alpha) for name in ("q_proj", "k_proj", "v_proj")], axis=-1)
    q, k, v = jnp.split(x if fault == "no_conv" else _short_conv(x, p["conv_weight"]), 3, axis=-1)
    q, k = jax.nn.silu(q).reshape(t, heads, dim), jax.nn.silu(k).reshape(t, heads, dim)
    v = (v if fault == "no_v_activation" else jax.nn.silu(v)).reshape(t, heads, dim)
    if fault != "no_l2norm":
        q, k = _l2norm(q), _l2norm(k)
    if fault != "no_q_scale":
        q = q / math.sqrt(dim)
    f = (u @ p["f_a_proj"]["kernel"]) @ p["f_b_proj"]["kernel"] + p["dt_bias"]
    g = -jnp.exp(p["A_log"])[None, :, None] * jax.nn.softplus(f).reshape(t, heads, dim)
    if fault == "scalar_decay":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(u @ p["b_proj"]["kernel"])
    # ... nor into the state, and does not decay it
    return x, q, k, v, g * real[..., None], beta * real


def kda(p, u, mask, *, heads, dim, eps, lora_alpha=16.0, fault=None):
    """``KDA(u)`` of ONE row ``u [T, hidden]`` from one layer's ``attn``
    subtree (float32); ``mask [T]`` 1 on real slots."""
    t = u.shape[0]
    u = u * mask.astype(F32)[:, None]  # a padded slot feeds nothing into the conv window
    _, q, k, v, g, beta = _kda_operands(p, u, mask, heads=heads, dim=dim, lora_alpha=lora_alpha, fault=fault)
    o, _ = delta_rule(q, k, v, g, beta, fault)
    o = _rms_norm(o, p["o_norm_scale"], eps).reshape(t, heads * dim)
    gate = (u @ p["g_a_proj"]["kernel"]) @ p["g_b_proj"]["kernel"]
    gate = jax.nn.silu(gate) if fault == "silu_gate" else jax.nn.sigmoid(gate)
    return _proj(p["o_proj"], o * gate, lora_alpha)


def latent_attention(p, u, mask, positions, *, heads, nope, rope, v_dim, eps, theta, lora_alpha=16.0, fault=None):
    """``Attn(u)`` of ONE row from one latent layer's ``attn`` subtree,
    expanded: per-head K and V built from the latent, no query latent."""
    t = u.shape[0]
    r = p["kv_a_norm"]["scale"].shape[0]
    kv_a = _proj(p["kv_a_proj"], u, lora_alpha)
    c = _rms_norm(kv_a[:, :r], p["kv_a_norm"]["scale"], eps)
    q = _proj(p["q_proj"], u, lora_alpha).reshape(t, heads, nope + rope)
    kv = (c @ p["kv_b_proj"]["kernel"]).reshape(t, heads, nope + v_dim)
    k_s = kv_a[:, None, r:]
    if fault == "mla_rope":
        k_s = _rotary(k_s, positions, theta, rope)
        q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], positions, theta, rope)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_s, (t, heads, rope))], axis=-1)
    v = kv[..., nope:]

    q_block = min(QUERY_BLOCK, t)
    n_blocks = -(-t // q_block)
    q_p = jnp.pad(q, ((0, n_blocks * q_block - t), (0, 0), (0, 0)))
    ki = jnp.arange(t)[None, :]

    def block(i):
        rows = jax.lax.dynamic_slice_in_dim(q_p, i * q_block, q_block, axis=0)
        qi = (i * q_block + jnp.arange(q_block))[:, None]
        visible = (ki <= qi) & (mask[None, :] > 0)
        scores = jnp.einsum("qhd,khd->hqk", rows, k) / math.sqrt(nope + rope)
        probs = jax.nn.softmax(jnp.where(visible[None], scores, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(block, jnp.arange(n_blocks)).reshape(n_blocks * q_block, heads * v_dim)[:t]
    return _proj(p["o_proj"], out, lora_alpha)


def _swiglu(p, n):
    return (jax.nn.silu(n @ p["gate_proj"]["kernel"]) * (n @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def gates(router_logits, bias, top_k, scaling, fault=None):
    """The dense gate matrix ``[..., router width]``: the sigmoid of each
    logit, kept at each token's ``top_k`` largest of ``score + bias``, those
    SCORES divided by their sum and multiplied by ``scaling``, zero elsewhere."""
    if fault == "softmax_router":
        scores = jax.nn.softmax(router_logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(router_logits)
    choosing = scores if fault == "no_selection_bias" else scores + bias
    kth = jnp.sort(choosing, axis=-1)[..., -top_k][..., None]
    g = jnp.where(choosing >= kth, scores, 0.0)
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
    """One expert layer alone, in float32: ``(routed, shared)``, the part of
    ``sum_e w_e E_e(n)`` that the experts held in ``mlp`` (``[first, first +
    held)`` of the router's width) give, and ``S(n)``."""
    with jax.default_matmul_precision("highest"):
        p = _up(mlp)
        n = jnp.asarray(n, F32)
        g = gates(n @ p["router"]["kernel"], p["router_bias"], top_k, scaling, fault)
        return _routed(p, n, g, first), _swiglu(p["shared_expert"], n)


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "v_dim", "eps", "theta", "kda_heads", "kda_dim", "top_k", "scaling", "first",
    "lora_alpha", "fault"))
def _layer(layer, x, mask, positions, *, heads, nope, rope, v_dim, eps, theta, kda_heads, kda_dim, top_k,
           scaling, first, lora_alpha=16.0, fault=None):
    """One row ``x [T, hidden]`` through one layer."""
    with jax.default_matmul_precision("highest"):
        p = _up(layer, fault)
        u = _rms_norm(x, p["ln_attn"]["scale"], eps)
        if "A_log" in p["attn"]:
            mixed = kda(p["attn"], u, mask, heads=kda_heads, dim=kda_dim, eps=eps, lora_alpha=lora_alpha, fault=fault)
        else:
            mixed = latent_attention(p["attn"], u, mask, positions, heads=heads, nope=nope, rope=rope, v_dim=v_dim,
                                     eps=eps, theta=theta, lora_alpha=lora_alpha, fault=fault)
        a = x + mixed
        n = _rms_norm(a, p["ln_mlp"]["scale"], eps)
        mlp = p["mlp"]
        if "router" not in mlp:  # the leading dense layer
            y = _swiglu(mlp, n)
        else:
            g = gates(n @ mlp["router"]["kernel"], mlp["router_bias"], top_k, scaling, fault)
            y = _routed(mlp, n, g, first)
            if fault != "no_shared_expert":
                y = y + _swiglu(mlp["shared_expert"], n)
        return a + y


@functools.partial(jax.jit, static_argnames=("eps", "fault"))
def _head(ln_f, lm_head, x, *, eps, fault=None):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, jnp.asarray(ln_f["scale"], F32), eps)
        return h @ _up(lm_head, fault)["kernel"]


def hidden(params, dims, input_ids, attention_mask, fault=None):
    """The residual stream ``[B, T, hidden]`` after the last layer, in
    float32, one row after another."""
    mask = jnp.asarray(attention_mask, jnp.int32)
    positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    embedding = _up(params["wte"], fault)["embedding"]
    linear = dims["linear_attn_config"]
    statics = dict(
        heads=int(dims["num_attention_heads"]),
        nope=int(dims["qk_nope_head_dim"]),
        rope=int(dims["qk_rope_head_dim"]),
        v_dim=int(dims["v_head_dim"]),
        eps=float(dims["rms_norm_eps"]),
        theta=float(dims["rope_theta"]),
        kda_heads=int(linear["num_heads"]),
        kda_dim=int(linear["head_dim"]),
        top_k=int(dims["num_experts_per_token"]),
        scaling=float(dims["routed_scaling_factor"]),
        first=int(dims.get("moe_first_expert_held", 0)),
        lora_alpha=float(dims.get("lora_alpha", 16.0)),
        fault=fault,
    )
    depth = int(dims["num_hidden_layers"])
    kinds = {int(l): "kda" for l in linear["kda_layers"]}
    kinds.update({int(l): "latent" for l in linear["full_attn_layers"]})  # numbered from 1
    for l in range(depth):
        runs = "kda" if "A_log" in params[f"h_{l}"]["attn"] else "latent"
        if kinds.get(l + 1) != runs:
            raise ValueError(f"layer {l + 1}: linear_attn_config says {kinds.get(l + 1)!r}, the tree runs {runs!r}")
    rows = []
    for b in range(mask.shape[0]):
        x = embedding[jnp.asarray(input_ids)[b]]
        for l in range(depth):
            x = _layer(params[f"h_{l}"], x, mask[b], positions[b], **statics)
        rows.append(x)
    return jnp.stack(rows)


@functools.partial(jax.jit, static_argnames=("heads", "dim", "lora_alpha", "at", "fault"))
def _kda_row_states(attn, u, mask, *, heads, dim, lora_alpha, at, fault=None):
    with jax.default_matmul_precision("highest"):
        p = _up(attn, fault)
        u = jnp.asarray(u, F32) * mask.astype(F32)[:, None]
        x, q, k, v, g, beta = _kda_operands(p, u, mask, heads=heads, dim=dim, lora_alpha=lora_alpha, fault=fault)
        window = p["conv_weight"].shape[0] - 1
        seen = jnp.concatenate([jnp.zeros((window, x.shape[1]), F32), x], axis=0)  # row j is slot j - window
        states, rows, S, start = [], [], None, 0
        for slot in at:  # the recurrence carried from one chosen slot to the next
            part = slice(start, slot + 1)
            _, S = delta_rule(q[part], k[part], v[part], g[part], beta[part], fault, S)
            states.append(S)
            rows.append(seen[slot + 1 : slot + 1 + window])
            start = slot + 1
        return jnp.stack(states), jnp.stack(rows)


def kda_states(params, dims, layer_inputs, attention_mask, at, fault=None):
    """``{layer: (S [len(at), B, heads, d, d], rows [len(at), B, K - 1, 3
    heads d])}``, float32: each KDA layer's state (key channels by value
    channels) and the last ``K - 1`` rows of ``[q~ | k~ | v~]`` in front of its
    convs after the slots ``at`` (ascending), when its mixer is given
    ``layer_inputs[layer]`` ``[B, T, hidden]`` (``u = N1(x)``, whoever computed
    it): the projections, convs, norms, gates and the recurrence alone, token
    by token. What a sampler's cache has to hold after a prefill of ``at[0] +
    1`` slots and one token a step from there, given the inputs ITS layers saw
    (``chipbench/kda_state_check.py``)."""
    mask = jnp.asarray(attention_mask, jnp.int32)
    linear = dims["linear_attn_config"]
    statics = dict(heads=int(linear["num_heads"]), dim=int(linear["head_dim"]),
                   lora_alpha=float(dims.get("lora_alpha", 16.0)), at=tuple(int(a) for a in at), fault=fault)
    out = {}
    for i, u in layer_inputs.items():
        attn = params[f"h_{i}"]["attn"]
        if "A_log" in attn:
            per_row = [_kda_row_states(attn, u[b], mask[b], **statics) for b in range(mask.shape[0])]
            out[i] = tuple(jnp.stack(leaf, axis=1) for leaf in zip(*per_row))
    return out


def logits(params, dims, input_ids, attention_mask, span, fault=None):
    """Float32 logits ``[B, span[1] - span[0], vocab]`` of the backbone tree
    ``params`` on ``input_ids`` [B, T] with ``attention_mask`` [B, T]."""
    x = hidden(params, dims, input_ids, attention_mask, fault)
    return _head(params["ln_f"], params["lm_head"], x[:, span[0] : span[1]],
                 eps=float(dims["rms_norm_eps"]), fault=fault)
