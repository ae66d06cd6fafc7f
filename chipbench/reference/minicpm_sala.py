"""MiniCPM-SALA's forward pass, plainly.

Written from the catalog row's ``config`` (``openbmb/MiniCPM-SALA``,
``model_type: minicpm_sala``), the lightning attention paper (Qin et al.,
arXiv:2401.04658), the MiniCPM4 report's block-sparse attention (InfLLM-V2,
arXiv:2506.07900, arXiv:2509.24663) and MiniCPM's published muP scalings.
``x`` is the residual stream, ``c = scale_depth / sqrt(32)`` with the
PUBLISHED depth 32 whatever ``num_hidden_layers`` a cut runs.

- Model: ``x_0 = scale_emb * Emb(id)``; block ``l``: ``a = x + c *
  Mixer_l(N1(x))``, ``y = a + c * MLP(N2(a))``, ``MLP(u) = Wd (silu(Wg u) *
  Wu u)``; ``logits = W_head (N_f(x_L) / (hidden / dim_model_base))``.
  RMSNorm eps ``rms_norm_eps``, no bias anywhere.
- ``mixer_types[l] == "lightning-attn"``, on ``u = N1(x)``: ``q, k, v = Wq
  u, Wk u, Wv u`` as ``lightning_nh`` heads of ``lightning_head_dim``; an
  RMSNorm with a learned scale over each head's q and k (one scale of the
  head's size for all heads); rotary embedding on all of a head's dims
  (pairs ``(i, i + d/2)``, ``rope_theta``, positions ``cumsum(mask) - 1``);
  per head, float32, ``S_t = lambda_h S_{t-1} + k_t v_t^T``, ``o_t = S_t^T
  q_t / sqrt(d)``, ``S_{-1} = 0``, no normaliser, ``lambda_h = exp(-2^(-8 h
  / H))`` for ``h = 1 .. H``; a padded slot feeds nothing into ``S``; ``out
  = Wo (N_o(concat_h o_t) * sigmoid(Wz u))``, ``N_o`` one RMSNorm over the
  joined heads. The recurrence runs token by token (``lax.scan``).
- ``mixer_types[l] == "minicpm4"``: ``q`` of ``num_attention_heads``, ``k, v``
  of ``num_key_value_heads`` heads of ``head_dim``, the per-head RMSNorm on q
  and k, NO rotary embedding. With ``sparse_config`` (``kernel_size`` K,
  ``kernel_stride`` s, ``block_size`` b, ``topk``, ``init_blocks``,
  ``window_size`` w, ``dense_len``) and positions counted from the row's
  first real token: compressed keys a KV head ``kbar_j = mean(k_{s j} ..
  k_{s j + K - 1})``; for the query at ``t`` and head ``h``, ``p_{h,t,.} =
  softmax_j(q_{h,t} . kbar_j / sqrt(d))`` over the ``j`` with ``s j + K - 1 <=
  t``; a KV group's score ``r_{g,t,j}`` the SUM of ``p`` over its query
  heads; block ``n`` (positions ``b n .. b n + b - 1``) scores ``max_j r``
  over the kernels that overlap it; blocks ``0 .. init_blocks - 1`` and every
  block with a position in ``(t - w, t]`` are chosen whatever they score, the
  best others until ``topk`` are chosen in all (ties to the lower block);
  ``o_{h,t}`` the softmax at ``1 / sqrt(d)`` over the positions ``<= t`` of
  the chosen blocks, V of the group; ``out = Wo (concat_h o * sigmoid(Wz
  u))``. A row of fewer than ``dense_len`` slots (the width of
  ``input_ids``) attends over every causal position.

Plain ``jax.numpy`` in float32 under ``highest`` matmul precision: no chunks
of a scan, no cache, no kernel, no roll of rows (keys are gathered by
position instead). It walks the system's own parameter tree one layer at a
time and casts that layer up, so it fits beside a trainer; a sparse layer
computes a block of ``Q_BLOCK`` queries of one row at a time, so that a row
of 16384 slots fits (a block's scores are ``[32, 128, 16384]`` float32). A
projection that carries a low-rank adapter adds ``(lora_alpha / r) x A B``
(``dims["lora_alpha"]``).

What the row's ``config`` and ``described_as`` do not settle is listed in
``chipbench/configs/minicpm-sala-9b-l8.json`` under ``assumed``, each with
its other reading; the other readings are planted faults here.

``fault`` plants a known error for the yardstick's control run:
``"first_blocks"`` (the FIRST ``topk`` blocks in place of the chosen: a
selection that ignores its scores), ``"max_for_sum"`` (a group's score the
max over its heads), ``"topk_beside_forced"`` (``topk`` blocks beside the
forced ones), ``"layer_scaled_slopes"`` (MiniMax-Text-01's ``slope * (1 - l
/ (L - 1) + 1e-5)``), ``"per_head_out_norm"`` (``N_o`` over each head),
``"no_depth_scale"`` (the ``1 / sqrt(32)`` dropped: ``c = scale_depth``),
``"dense_attention"`` (no selection at all), ``"no_rotary"`` (lightning
layers without), ``"no_gate"`` (both kinds without their sigmoid gate).
Controls for precision, not faults: ``"fp8_weights"`` (every matrix rounded
to ``float8_e4m3fn``, the nearest precision below the stated bfloat16) and
``"bf16_state"`` (``S`` rounded to bfloat16 after every token, the nearest
precision below the state's stated float32).

``layer_states`` gives a lightning layer's ``S`` after chosen slots from given
layer inputs, by the same recurrence: what a sampler's cache has to hold
(``chipbench/state_check.py``, where ``bf16_state`` is the control that has to
read not correct).
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = ("first_blocks", "max_for_sum", "topk_beside_forced", "layer_scaled_slopes",
          "per_head_out_norm", "no_depth_scale", "dense_attention", "no_rotary", "no_gate")
PRECISION_CONTROLS = ("fp8_weights", "bf16_state")
PUBLISHED_DEPTH = 32  # scale_depth / sqrt(this), in a cut too
Q_BLOCK = 128


def _up(tree, fault=None):
    def up(x):
        if fault == "fp8_weights" and x.ndim == 2:
            x = jnp.asarray(x, F32).astype(jnp.float8_e4m3fn)
        return jnp.asarray(x, F32)

    return jax.tree_util.tree_map(up, tree)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _proj(p, x, lora_alpha):
    y = x @ p["kernel"]
    if "lora_a" in p:
        y = y + (lora_alpha / p["lora_a"].shape[1]) * ((x @ p["lora_a"]) @ p["lora_b"])
    return y


def _rotary(x, positions, theta):
    """x [B, T, H, D]; pairs are (i, i + D/2)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (float(theta) ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions[..., None].astype(F32) * inv_freq
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _lightning(p, u, mask, positions, d, layer, fault, state_at=()):
    """The mixer's output, and ``S`` after each slot of ``state_at`` (ascending)."""
    b, t, _ = u.shape
    H, D, eps, alpha = d["lightning_nh"], d["lightning_head_dim"], d["rms_norm_eps"], d["lora_alpha"]
    q = _proj(p["q_proj"], u, alpha).reshape(b, t, H, D)
    k = _proj(p["k_proj"], u, alpha).reshape(b, t, H, D)
    v = _proj(p["v_proj"], u, alpha).reshape(b, t, H, D)
    q, k = _rms_norm(q, p["q_norm"]["scale"], eps), _rms_norm(k, p["k_norm"]["scale"], eps)
    if fault != "no_rotary":
        q, k = _rotary(q, positions, d["rope_theta"]), _rotary(k, positions, d["rope_theta"])
    v = v * mask[:, :, None, None].astype(F32)
    slopes = 2.0 ** (-8.0 * jnp.arange(1, H + 1, dtype=F32) / H)
    if fault == "layer_scaled_slopes":
        slopes = slopes * (1.0 - layer / max(d["num_hidden_layers"] - 1, 1) + 1e-5)
    decay = jnp.exp(-slopes)[None, :, None, None]

    def token(S, inp):  # S [B, H, Dk, Dv]
        q_t, k_t, v_t = inp
        S = decay * S + k_t[..., :, None] * v_t[..., None, :]
        if fault == "bf16_state":  # (a convert to bfloat16 and back is elided on the chip: xla_allow_excess_precision)
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t) / math.sqrt(D)

    over_t = lambda a: jnp.moveaxis(a, 1, 0)
    S, outs, kept, start = jnp.zeros((b, H, D, D), F32), [], [], 0
    for stop in [a + 1 for a in state_at] + [t]:  # the one recurrence, read where a caller asks
        S, o = jax.lax.scan(token, S, tuple(over_t(a)[start:stop] for a in (q, k, v)))
        outs.append(o)
        kept.append(S)
        start = stop
    o = jnp.moveaxis(jnp.concatenate(outs), 0, 1)  # [B, T, H, D]
    if fault == "per_head_out_norm":
        o = _rms_norm(o, p["o_norm"]["scale"].reshape(H, D), eps).reshape(b, t, H * D)
    else:
        o = _rms_norm(o.reshape(b, t, H * D), p["o_norm"]["scale"], eps)
    if fault != "no_gate":
        o = o * jax.nn.sigmoid(u @ p["z_proj"]["kernel"])
    return _proj(p["o_proj"], o, alpha), jnp.stack(kept[: len(state_at)]) if state_at else None


def _chosen_blocks(q, kbar, t, n_blocks, d, fault):
    """``[KV, Q, n_blocks]`` bool for queries ``q [Q, H, D]`` at positions ``t
    [Q]`` over the compressed keys ``kbar [NK, KV, D]`` of one row."""
    sc = d["sparse_config"]
    K, s, blk, topk = sc["kernel_size"], sc["kernel_stride"], sc["block_size"], sc["topk"]
    Q, H, D = q.shape
    NK, KV = kbar.shape[0], kbar.shape[1]
    scores = jnp.einsum("qkgd,jkd->kgqj", q.reshape(Q, KV, H // KV, D), kbar) / math.sqrt(D)
    done = s * jnp.arange(NK)[None, :] + K - 1 <= t[:, None]  # [Q, NK]
    p = jax.nn.softmax(jnp.where(done, scores, -1e30), axis=-1) * done
    r = jnp.max(p, axis=1) if fault == "max_for_sum" else jnp.sum(p, axis=1)  # [KV, Q, NK]
    # kernel j covers positions [s j, s j + K - 1], block n [blk n, blk n + blk - 1]
    j, n = jnp.arange(NK)[None, :], jnp.arange(n_blocks)[:, None]
    overlap = (s * j + K - 1 >= blk * n) & (s * j <= blk * n + blk - 1)  # [n_blocks, NK]
    usable = overlap[None, None] & done[None, :, None, :]  # [1, Q, n_blocks, NK]
    score = jnp.max(jnp.where(usable, r[:, :, None, :], -jnp.inf), axis=-1)  # [KV, Q, n_blocks]
    blocks = jnp.arange(n_blocks)[None, :]
    causal = blocks <= (t // blk)[:, None]
    forced = (blocks < sc["init_blocks"]) | (blocks >= (jnp.maximum(t - sc["window_size"] + 1, 0) // blk)[:, None])
    forced = forced & causal
    if fault == "first_blocks":
        return jnp.broadcast_to((blocks < topk) & causal, (KV, Q, n_blocks))
    free = causal & ~forced
    room = topk - jnp.sum(forced, axis=-1, keepdims=True) * (fault != "topk_beside_forced")  # [Q, 1]
    # rank of each free block among the free ones, best first, ties to the lower block: a stable sort
    order = jnp.argsort(jnp.where(free, -score, jnp.inf), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return forced[None] | (free[None] & (rank < room[None]))


def _sparse(p, u, mask, d, fault):
    b, t, _ = u.shape
    H, KV, D = d["num_attention_heads"], d["num_key_value_heads"], d["head_dim"]
    eps, alpha, sc = d["rms_norm_eps"], d["lora_alpha"], d["sparse_config"]
    q = _rms_norm(_proj(p["q_proj"], u, alpha).reshape(b, t, H, D), p["q_norm"]["scale"], eps)
    k = _rms_norm(_proj(p["k_proj"], u, alpha).reshape(b, t, KV, D), p["k_norm"]["scale"], eps)
    v = _proj(p["v_proj"], u, alpha).reshape(b, t, KV, D)
    selects = t >= sc["dense_len"] and fault != "dense_attention"
    K, s, blk = sc["kernel_size"], sc["kernel_stride"], sc["block_size"]
    n_kernels, n_blocks = max((t - K) // s + 1, 1), -(-t // blk)
    slots = jnp.arange(t)

    def row(q_r, k_r, v_r, m_r):
        first = jnp.argmax(m_r > 0)  # the row's first real slot: position 0
        pos = slots - first  # a slot's position (negative: a pad in front)
        real = m_r > 0
        # compressed keys: the mean of the keys at positions [s j, s j + K)
        at = first + s * jnp.arange(n_kernels)[:, None] + jnp.arange(K)[None, :]  # [NK, K] slots
        inside = at < t
        members = jnp.where((inside & real[jnp.minimum(at, t - 1)])[..., None, None], k_r[jnp.minimum(at, t - 1)], 0.0)
        kbar = jnp.sum(members, axis=1) / K  # [NK, KV, D]

        pad = -t % Q_BLOCK
        q_p, pos_p = jnp.pad(q_r, ((0, pad), (0, 0), (0, 0))), jnp.pad(pos, (0, pad))

        def block(start):
            qs = jax.lax.dynamic_slice_in_dim(q_p, start, Q_BLOCK, axis=0)  # [Q, H, D]
            t_q = jax.lax.dynamic_slice_in_dim(pos_p, start, Q_BLOCK)
            visible = (slots[None, :] <= (start + jnp.arange(Q_BLOCK))[:, None]) & real[None, :]  # [Q, T]
            keep = visible[None]
            if selects:
                chosen = _chosen_blocks(qs, kbar, jnp.maximum(t_q, 0), n_blocks, d, fault)  # [KV, Q, NB]
                of_block = jnp.clip(pos // blk, 0, n_blocks - 1)
                keep = keep & chosen[:, :, of_block]  # [KV, Q, T]
            scores = jnp.einsum("qkgd,skd->kgqs", qs.reshape(Q_BLOCK, KV, H // KV, D), k_r) / math.sqrt(D)
            probs = jax.nn.softmax(jnp.where(keep[:, None], scores, -1e30), axis=-1)
            return jnp.einsum("kgqs,skd->qkgd", probs, v_r).reshape(Q_BLOCK, H * D)

        out = jax.lax.map(block, jnp.arange(0, t + pad, Q_BLOCK))
        return out.reshape(t + pad, H * D)[:t]

    out = jnp.stack([row(q[i], k[i], v[i], mask[i]) for i in range(b)])
    if fault != "no_gate":
        out = out * jax.nn.sigmoid(u @ p["z_proj"]["kernel"])
    return _proj(p["o_proj"], out, alpha)


def _freeze(value):
    if isinstance(value, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_freeze(v) for v in value)
    return value


def _thaw(value):
    if isinstance(value, tuple) and value and all(isinstance(v, tuple) and len(v) == 2 and isinstance(v[0], str) for v in value):
        return {k: _thaw(v) for k, v in value}
    return value


LAYER_DIMS = ("num_attention_heads", "num_key_value_heads", "head_dim", "lightning_nh", "lightning_head_dim",
              "rms_norm_eps", "rope_theta", "scale_depth", "num_hidden_layers", "sparse_config", "lora_alpha")


@functools.partial(jax.jit, static_argnames=("dims", "kind", "layer", "fault"))
def _layer(tree, x, mask, positions, *, dims, kind, layer, fault=None):
    d = {k: _thaw(v) for k, v in zip(LAYER_DIMS, dims)}
    with jax.default_matmul_precision("highest"):
        p = _up(tree, fault)
        eps = d["rms_norm_eps"]
        c = d["scale_depth"] / (1.0 if fault == "no_depth_scale" else math.sqrt(PUBLISHED_DEPTH))
        u = _rms_norm(x, p["ln_attn"]["scale"], eps)
        if kind == "lightning-attn":
            mixed, _ = _lightning(p["attn"], u, mask, positions, d, layer, fault)
        else:
            mixed = _sparse(p["attn"], u, mask, d, fault)
        a = x + c * mixed
        h = _rms_norm(a, p["ln_mlp"]["scale"], eps)
        inner = jax.nn.silu(h @ p["mlp"]["gate_proj"]["kernel"]) * (h @ p["mlp"]["up_proj"]["kernel"])
        return a + c * (inner @ p["mlp"]["down_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("dims", "layer", "fault", "state_at"))
def _mixer_states(tree, u, mask, positions, *, dims, layer, fault, state_at):
    d = {k: _thaw(v) for k, v in zip(LAYER_DIMS, dims)}
    with jax.default_matmul_precision("highest"):
        return _lightning(_up(tree, fault), jnp.asarray(u, F32), mask, positions, d, layer, fault, state_at)[1]


@functools.partial(jax.jit, static_argnames=("eps", "divisor", "fault"))
def _head(ln_f, lm_head, x, *, eps, divisor, fault=None):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, jnp.asarray(ln_f["scale"], F32), eps) / divisor
        return h @ _up(lm_head, fault)["kernel"]


def _positions(attention_mask):
    mask = jnp.asarray(attention_mask, jnp.int32)
    return mask, jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)


def _layer_dims(dims):
    d = dict(dims)
    d.setdefault("lora_alpha", 16.0)
    return tuple(_freeze(d[k]) for k in LAYER_DIMS)


def hidden(params, dims, input_ids, attention_mask, fault=None):
    """The residual stream ``[B, T, hidden]`` after the last layer, float32."""
    mask, positions = _positions(attention_mask)
    x = _up(params["wte"], fault)["embedding"][jnp.asarray(input_ids)] * float(dims["scale_emb"])
    for i in range(int(dims["num_hidden_layers"])):
        x = _layer(params[f"h_{i}"], x, mask, positions, dims=_layer_dims(dims), kind=dims["mixer_types"][i],
                   layer=i, fault=fault)
    return x


def layer_states(params, dims, layer_inputs, attention_mask, at, fault=None):
    """``{layer: S [len(at), B, heads, d_k, d_v]}``, float32: each lightning
    layer's state after the slots ``at`` (ascending) when its mixer is given
    ``layer_inputs[layer]`` ``[B, T, hidden]`` (``u = N1(x)``, whoever computed
    it): the mixer's projections, norms, rotary and recurrence alone. What a
    sampler's cache has to hold after a prefill of ``at[0] + 1`` slots and one
    token a step from there, given the inputs ITS layers saw
    (``chipbench/state_check.py``)."""
    mask, positions = _positions(attention_mask)
    return {i: _mixer_states(params[f"h_{i}"]["attn"], u, mask, positions, dims=_layer_dims(dims), layer=i,
                             fault=fault, state_at=tuple(at))
            for i, u in layer_inputs.items() if dims["mixer_types"][i] == "lightning-attn"}


def logits(params, dims, input_ids, attention_mask, span, fault=None):
    """Float32 logits ``[B, span[1] - span[0], vocab]`` of the backbone tree
    ``params`` on ``input_ids`` [B, T] with ``attention_mask`` [B, T]."""
    x = hidden(params, dims, input_ids, attention_mask, fault)
    return _head(params["ln_f"], params["lm_head"], x[:, span[0] : span[1]],
                 eps=float(dims["rms_norm_eps"]),
                 divisor=float(dims["hidden_size"]) / float(dims["dim_model_base"]), fault=fault)
