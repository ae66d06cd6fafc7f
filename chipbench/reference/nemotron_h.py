"""NVIDIA-Nemotron-3-Nano-30B-A3B's forward pass, plainly.

Written from the catalog row's ``config`` (``nvidia/NVIDIA-Nemotron-3-Nano-
30B-A3B-BF16``, ``model_type: nemotron_h``), the Mamba-2 paper (Dao & Gu
2024, arXiv:2405.21060) and the Nemotron-H report's description of the stack
(layers of one sublayer each; no position embeddings, the state-space layers
carry order). ``h`` is the residual stream, ``u`` a layer's normed input,
``d`` the hidden size. RMSNorm everywhere (``x * rsqrt(mean(x^2) + norm_eps) *
w``), no bias on any projection, no multiplier anywhere, the head untied.

- Model: ``h_0 = E[id]``; layer ``i``: ``h <- h + f_i(RMSNorm_i(h))``, ``f_i``
  by ``hybrid_override_pattern[i]``; ``logits = RMSNorm_f(h_L) W_head``.
- ``M`` (Mamba-2: ``mamba_num_heads`` heads of ``mamba_head_dim``, ``n_groups``
  groups, state ``ssm_state_size``, conv ``conv_kernel``): ``[z | xBC | dt] =
  W_in u``; ``xBC = silu(conv1d(xBC) + b)``, causal, depthwise; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head ``j`` of group ``j //
  (heads / groups)``: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t``,
  ``y_t = S_t C_t + D x_t``; ``y = y * silu(z)`` (the gate FIRST), RMSNorm over
  each group's channels, times the scale; ``W_out``.
- ``*``: ``q = W_q u`` as ``num_attention_heads`` heads of ``head_dim``, ``k``
  and ``v`` as ``num_key_value_heads``; causal ``softmax(q k^T /
  sqrt(head_dim))``, a KV head serving ``heads / kv heads`` query heads;
  ``W_o``. NO rotary embedding and no other positional term.
- ``E``: ``s = sigmoid(W_r u)`` over the router's width; ``chosen = top_k(s +
  b)``; ``gate_e = routed_scaling_factor * s_e / sum_chosen s``; ``sum_e gate_e
  W2_e relu(W1_e u)^2 + V2 relu(V1 u)^2``: two matrices an expert, the shared
  expert (every token, unweighted) of its own width.

Plain ``jax.numpy`` in float32 under ``highest`` matmul precision, one row at
a time: no chunks, no cache, no kernel, no batching. The recurrence is a
``lax.scan`` over single tokens, the conv is shifted adds, attention a full
masked softmax, the gate a dense ``[tokens, router width]`` matrix, every held
expert applied to every token. It walks the system's own parameter tree one
layer at a time and casts that layer up.

**One chip's share.** The expert kernels hold ``dims["n_routed_experts"]``
experts, the slice ``[first, first + held)`` of the router's width (``first``
is ``dims["moe_first_expert_held"]``, 0 where absent); the reference routes
over the whole width, renormalises over all the chosen and adds only what the
held experts give, and the shared expert whole. ``moe_layer`` is that one
layer alone, for the test that the shares add up to the uncut layer. The
vocabulary is whatever slice the embedding and the head hold.

Padding (not in the publication): a padded position contributes nothing. ``u``
is zeroed there before ``W_in`` and ``xBC`` again after the conv (its bias is
not zero), so a left-padded row reaches its first real token with ``S = 0``
and a zero conv window; a padded key is masked in attention.

What the row's ``config`` does not settle is listed in
``chipbench/configs/nemotron3-nano-30b-a3b-l9e8.json`` under ``assumed``; the
other readings that can be told apart are planted faults.

``fault`` plants a known error for the yardstick's control run:
``"state_reset_per_chunk"`` (``S = 0`` at every ``chunk_size``-th slot: a lost
carry between chunks), ``"no_conv_bias"``, ``"norm_before_gate"`` (the grouped
norm on ``y``, then ``* silu(z)``), ``"ungrouped_gated_norm"`` (one RMSNorm
over all of ``d_ssm``), ``"rotary_on_attention"`` (split-half rotary at
``rope_theta`` over the whole head, as the config's keys suggest),
``"relu_not_squared"``, ``"gated_experts"`` (``silu(a) * a`` for ``relu(a)^2``,
``a = W1 u``: what a gated path handed the one matrix in both places
computes), ``"shared_width_as_routed"`` (the shared expert's first
``moe_intermediate_size`` units alone), ``"no_routed_scaling"``,
``"no_renormalize"`` (``gate_e = 2.5 s_e``), ``"softmax_router"``,
``"no_selection_bias"``, ``"second_sublayer_norm"`` (the stack read as blocks
of two sublayers: a mixer layer that an ``E`` layer follows runs as norm,
mixer, the next layer's norm, the next layer's experts, and the ``E`` layer
then runs as well: the experts twice). The control for precision, not a fault:
``"fp8_weights"`` (every matrix rounded to ``float8_e4m3fn``, the nearest
precision below the stated bfloat16).
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = ("state_reset_per_chunk", "no_conv_bias", "norm_before_gate", "ungrouped_gated_norm", "rotary_on_attention",
          "relu_not_squared", "gated_experts", "shared_width_as_routed", "no_routed_scaling", "no_renormalize",
          "softmax_router", "no_selection_bias", "second_sublayer_norm")
PRECISION_CONTROLS = ("fp8_weights",)
KINDS = {"M": "mixer", "*": "attn", "E": "mlp"}  # the subtree a layer of each letter holds beside its one norm
# the configuration's keys a layer reads, in the order `_layer` takes them
LAYER_DIMS = ("num_attention_heads", "num_key_value_heads", "head_dim", "norm_eps", "rope_theta", "mamba_num_heads",
              "mamba_head_dim", "n_groups", "ssm_state_size", "chunk_size", "num_experts_per_tok", "routed_scaling_factor",
              "moe_intermediate_size")


def _up(tree, fault=None):
    def up(x):
        if fault == "fp8_weights" and x.ndim >= 2:
            x = jnp.asarray(x, F32).astype(jnp.float8_e4m3fn)
        return jnp.asarray(x, F32)

    return jax.tree_util.tree_map(up, tree)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x, positions, theta):
    """x [T, H, D]: all ``D`` columns rotated, pairs ``(i, i + D/2)`` (the planted fault's)."""
    dim = x.shape[-1]
    inv_freq = 1.0 / (float(theta) ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    ang = positions[:, None].astype(F32) * inv_freq
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mamba2(p, u, mask, d, fault=None):
    """The ``M`` mixer on ONE row ``u [T, d]`` from a layer's ``mixer`` subtree
    (float32), the recurrence token by token; ``mask [T]`` 1 on real slots."""
    T = u.shape[0]
    H, P, G, N = d["mamba_num_heads"], d["mamba_head_dim"], d["n_groups"], d["ssm_state_size"]
    d_ssm, gn = H * P, G * N
    real = mask.astype(F32)[:, None]
    proj = (u * real) @ p["in_proj"]["kernel"]
    z, xbc, dt = proj[:, :d_ssm], proj[:, d_ssm : 2 * d_ssm + 2 * gn], proj[:, 2 * d_ssm + 2 * gn :]

    w = p["conv_weight"]  # [K, C], the last row is the current token's tap
    taps = w.shape[0]
    conv = xbc * w[taps - 1]
    for back in range(1, taps):  # the token `back` places to the left
        conv = conv + jnp.pad(xbc, ((back, 0), (0, 0)))[:T] * w[taps - 1 - back]
    if fault != "no_conv_bias":
        conv = conv + p["conv_bias"]
    xbc = jax.nn.silu(conv) * real
    x = xbc[:, :d_ssm].reshape(T, H, P)
    Bm = jnp.repeat(xbc[:, d_ssm : d_ssm + gn].reshape(T, G, N), H // G, axis=1)
    Cm = jnp.repeat(xbc[:, d_ssm + gn :].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # [T, H]; the published limits are 0 and infinity: no clamp
    A = -jnp.exp(p["A_log"])

    def token(S, inp):
        x_t, b_t, c_t, dt_t, slot = inp
        if fault == "state_reset_per_chunk":
            S = jnp.where(slot % d["chunk_size"] == 0, 0.0, S)
        S = jnp.exp(dt_t * A)[:, None, None] * S + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, c_t) + p["D"][:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), F32), (x, Bm, Cm, dt, jnp.arange(T)))
    y = y.reshape(T, d_ssm)
    groups = 1 if fault == "ungrouped_gated_norm" else G

    def grouped_norm(a):
        a = a.reshape(T, groups, d_ssm // groups)
        return (a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True) + d["norm_eps"])).reshape(T, d_ssm)

    if fault == "norm_before_gate":
        y = grouped_norm(y) * p["norm_scale"] * jax.nn.silu(z)
    else:
        y = grouped_norm(y * jax.nn.silu(z)) * p["norm_scale"]
    return y @ p["out_proj"]["kernel"]


def attention(p, u, mask, positions, d, fault=None):
    """The ``*`` mixer on ONE row from a layer's ``attn`` subtree: no positional term."""
    T = u.shape[0]
    heads, kv_heads, D = d["num_attention_heads"], d["num_key_value_heads"], d["head_dim"]
    q = (u @ p["q_proj"]["kernel"]).reshape(T, heads, D)
    k = (u @ p["k_proj"]["kernel"]).reshape(T, kv_heads, D)
    v = (u @ p["v_proj"]["kernel"]).reshape(T, kv_heads, D)
    if fault == "rotary_on_attention":
        q, k = _rotary(q, positions, d["rope_theta"]), _rotary(k, positions, d["rope_theta"])
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)  # KV head j serves query heads [j group, (j + 1) group)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(D)
    at = jnp.arange(T)
    visible = (at[None, :] <= at[:, None]) & (mask[None, :] > 0)
    probs = jax.nn.softmax(jnp.where(visible[None], scores, -1e30), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(T, heads * D) @ p["o_proj"]["kernel"]


def gates(router_logits, bias, top_k, scaling, fault=None):
    """The dense gate matrix ``[..., router width]``: the sigmoid of each
    logit, kept at each token's ``top_k`` largest of ``score + bias``, those
    SCORES over their sum, times ``scaling``, zero elsewhere."""
    scores = jax.nn.softmax(router_logits, axis=-1) if fault == "softmax_router" else jax.nn.sigmoid(router_logits)
    choosing = scores if fault == "no_selection_bias" else scores + bias
    kth = jnp.sort(choosing, axis=-1)[..., -top_k][..., None]
    g = jnp.where(choosing >= kth, scores, 0.0)
    if fault != "no_renormalize":
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return g if fault == "no_routed_scaling" else g * scaling


def _act(a, fault=None):
    if fault == "relu_not_squared":
        return jax.nn.relu(a)
    if fault == "gated_experts":
        return jax.nn.silu(a) * a
    return jnp.square(jax.nn.relu(a))


def _routed(p, n, g, first, fault=None):
    """The held experts' part: expert ``first + e`` on every token, plainly."""
    y = jnp.zeros_like(n)
    for e in range(p["w_up"].shape[0]):
        y = y + g[..., first + e : first + e + 1] * (_act(n @ p["w_up"][e], fault) @ p["w_down"][e])
    return y


def _shared(p, n, width, fault=None):
    up, down = p["up_proj"]["kernel"], p["down_proj"]["kernel"]
    if fault == "shared_width_as_routed":
        up, down = up[:, :width], down[:width]
    return _act(n @ up, fault) @ down


def moe_layer(mlp, n, top_k, scaling, first=0, fault=None, shared=True):
    """One ``E`` layer's sublayer alone, in float32: the part of ``sum_e gate_e
    E_e(n)`` that the experts held in ``mlp`` (``[first, first + held)`` of the
    router's width) give and, with ``shared``, the shared expert whole."""
    with jax.default_matmul_precision("highest"):
        p = _up(mlp)
        n = jnp.asarray(n, F32)
        y = _routed(p, n, gates(n @ p["router"]["kernel"], p["router_bias"], top_k, scaling, fault), first, fault)
        return y + _shared(p["shared_expert"], n, p["w_up"].shape[-1], fault) if shared else y


def _experts(p, norm, x, d, first, fault):
    n = _rms_norm(x, norm["scale"], d["norm_eps"])
    g = gates(n @ p["router"]["kernel"], p["router_bias"], d["num_experts_per_tok"], d["routed_scaling_factor"], fault)
    return _routed(p, n, g, first, fault) + _shared(p["shared_expert"], n, d["moe_intermediate_size"], fault)


@functools.partial(jax.jit, static_argnames=("letter", "dims", "first", "fault"))
def _layer(layer, after, x, mask, positions, *, letter, dims, first, fault=None):
    """One row ``x [T, d]`` through one layer of kind ``letter``; ``after`` is
    the next layer's tree where the planted fault ``second_sublayer_norm``
    reads it (an ``E`` layer behind a mixer layer), else None."""
    d = dict(zip(LAYER_DIMS, dims))
    with jax.default_matmul_precision("highest"):
        p = _up(layer, fault)
        if letter == "E":
            return x + _experts(p["mlp"], p["ln_mlp"], x, d, first, fault)
        u = _rms_norm(x, p["ln_attn"]["scale"], d["norm_eps"])
        if letter == "M":
            x = x + mamba2(p["mixer"], u, mask, d, fault)
        else:
            x = x + attention(p["attn"], u, mask, positions, d, fault)
        if after is not None:
            nxt = _up(after, fault)
            x = x + _experts(nxt["mlp"], nxt["ln_mlp"], x, d, first, fault)
        return x


@functools.partial(jax.jit, static_argnames=("eps", "fault"))
def _head(ln_f, lm_head, x, *, eps, fault=None):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, jnp.asarray(ln_f["scale"], F32), eps) @ _up(lm_head, fault)["kernel"]


def hidden(params, dims, input_ids, attention_mask, fault=None):
    """The residual stream ``[B, T, d]`` after the last layer, in float32, one
    row after another."""
    mask = jnp.asarray(attention_mask, jnp.int32)
    positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    embedding = _up(params["wte"], fault)["embedding"]
    depth = int(dims["num_hidden_layers"])
    pattern = str(dims["hybrid_override_pattern"])[:depth]
    for i, letter in enumerate(pattern):
        if set(params[f"h_{i}"]) - {"ln_attn", "ln_mlp"} != {KINDS[letter]}:
            raise ValueError(f"layer {i}: hybrid_override_pattern says {letter!r}, the tree holds {sorted(params[f'h_{i}'])}")
    layer_dims = tuple(dims[k] for k in LAYER_DIMS)
    statics = dict(dims=layer_dims, first=int(dims.get("moe_first_expert_held", 0)), fault=fault)
    rows = []
    for b in range(mask.shape[0]):
        x = embedding[jnp.asarray(input_ids)[b]]
        for i, letter in enumerate(pattern):
            twice = fault == "second_sublayer_norm" and letter != "E" and pattern[i + 1 : i + 2] == "E"
            x = _layer(params[f"h_{i}"], params[f"h_{i + 1}"] if twice else None, x, mask[b], positions[b], letter=letter, **statics)
        rows.append(x)
    return jnp.stack(rows)


def logits(params, dims, input_ids, attention_mask, span, fault=None):
    """Float32 logits ``[B, span[1] - span[0], vocab]`` of the backbone tree
    ``params`` on ``input_ids`` [B, T] with ``attention_mask`` [B, T]."""
    x = hidden(params, dims, input_ids, attention_mask, fault)
    return _head(params["ln_f"], params["lm_head"], x[:, span[0] : span[1]], eps=float(dims["norm_eps"]), fault=fault)
