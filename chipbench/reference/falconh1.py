"""Falcon-H1's forward pass, plainly.

Written from the published ``config.json`` of
``tiiuae/Falcon-H1-34B-Instruct`` (``model_type: falcon_h1``), the Mamba-2
paper (Dao & Gu 2024, arXiv:2405.21060) and the catalog row's description
("parallel Mamba-2 + attention heads per block"). ``x`` is the residual
stream, lower-case names are the config's fixed scalar multipliers.

- Model: ``x0 = wte[ids] * embedding_multiplier``; the blocks; ``logits =
  (RMSNorm(x_L) @ W_head) * lm_head_multiplier``. RMSNorm eps
  ``rms_norm_eps``; no bias anywhere but on the conv.
- Block: ``u = RMSNorm_in(x)``; ``x = x + ssm_out_multiplier * Mixer(u) +
  attention_out_multiplier * Attn(attention_in_multiplier * u)``; then ``x =
  x + MLP(RMSNorm_ff(x))``. Mixer and attention read the same ``u``.
- Attention: ``k = (u W_k) * key_multiplier``; ``num_attention_heads`` query
  heads over ``num_key_value_heads`` KV heads of ``head_dim``; rotary over
  the whole head (split-half pairing, ``rope_theta``); causal softmax at
  ``1 / sqrt(head_dim)``.
- MLP: ``down(silu(gate(h) * mlp_multipliers[0]) * up(h)) *
  mlp_multipliers[1]``.
- Mixer (Mamba-2: ``mamba_n_heads`` heads of ``mamba_d_head``,
  ``mamba_n_groups`` groups, state ``mamba_d_state``, conv ``mamba_d_conv``):
  ``p = (u * ssm_in_multiplier) W_in``, then ``p`` times ``ssm_multipliers``
  on its segments ``z | x | B | C | dt``; ``xBC = silu(conv1d(xBC) + b)``,
  causal, depthwise; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  per head ``h`` of group ``h // (heads / groups)``: ``S_t = exp(dt_t A)
  S_{t-1} + dt_t x_t (outer) B_t``, ``y_t = S_t C_t + D x_t``; ``y = y *
  silu(z)``, RMSNorm over each group's channels, times the scale; ``W_out``.

Plain ``jax.numpy`` in float32 under ``highest`` matmul precision: no chunks,
no cache, no kernel. The recurrence is a ``lax.scan`` over single tokens, the
conv is shifted adds, attention is a full masked softmax. It walks the
system's own parameter tree one layer at a time and casts that layer up, so
it fits beside a trainer. The sizes come from ``dims``, the published keys of
the configuration file.

Departures, and what the row's ``config`` and ``described_as`` do not settle:

- The segment order of the multiplier vector, ``z, x, B, C, dt`` for
  ``ssm_multipliers[0..4]``, follows the order of ``in_proj``'s outputs in
  Mamba-2 (``z | xBC | dt``); the config gives five numbers and no names.
- The gated norm is taken over each of the ``mamba_n_groups`` groups'
  channels (``mamba_d_ssm / mamba_n_groups``), as Mamba-2's grouped RMSNorm
  does when B and C have groups; the config says ``mamba_rms_norm`` and
  ``mamba_norm_before_gate: false`` and not the grouping.
- ``mamba_d_ssm`` (4096) sets the mixer's inner width; ``mamba_expand`` (2,
  which would give 10240) is not used. The MLP width is
  ``intermediate_size``.
- Padding (not in the publication, which has no padded batches in its
  equations): a padded position contributes nothing. ``u`` is zeroed there
  before ``W_in`` and ``xBC`` again after the conv (its bias is not zero), so
  a left-padded row reaches its first real token with ``S = 0`` and a zero
  conv window; ``dt`` at a pad only decays the state. Positions are
  ``cumsum(mask) - 1``.
- Mamba-2's clamp of ``dt`` to ``time_step_limit`` (0, inf) is the identity
  and is left out.

``fault`` plants a known error for the yardstick's control run:
``"no_rotary"``, ``"strict_causal"`` (a position hidden from itself),
``"state_reset_per_chunk"`` (``S = 0`` at every ``mamba_chunk_size``-th slot:
what a lost carry between chunks does), ``"no_conv"`` (only the conv's
current-token tap: what a lost conv window does), ``"no_ssm_multipliers"``,
``"no_key_multiplier"``, ``"mixer_after_attention"`` (the mixer reads the
normed residual AFTER attention was added: sequential, not parallel).
``"fp8_weights"`` is the control for precision, not a fault: every matrix
rounded to ``float8_e4m3fn``, the nearest precision below the stated bf16.
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = ("no_rotary", "strict_causal", "state_reset_per_chunk", "no_conv",
          "no_ssm_multipliers", "no_key_multiplier", "mixer_after_attention")
# not a fault of the mathematics but the control for precision: every matrix
# rounded to float8_e4m3fn, the nearest precision below the stated bfloat16
PRECISION_CONTROL = "fp8_weights"
# the configuration's keys a layer reads, in the order _layer takes them
LAYER_DIMS = ("num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
              "rope_theta", "mamba_n_heads", "mamba_d_head", "mamba_n_groups", "mamba_d_state",
              "mamba_chunk_size", "attention_in_multiplier", "attention_out_multiplier",
              "key_multiplier", "mlp_multipliers", "ssm_in_multiplier", "ssm_out_multiplier",
              "ssm_multipliers")


def _up(tree, fault=None):
    def up(x):
        if fault == PRECISION_CONTROL and x.ndim == 2:
            x = jnp.asarray(x, F32).astype(jnp.float8_e4m3fn)
        return jnp.asarray(x, F32)

    return jax.tree_util.tree_map(up, tree)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x, positions, theta):
    """x [B, T, H, D]; pairs are (i, i + D/2)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (float(theta) ** (jnp.arange(0, d, 2, dtype=F32) / d))  # 1e11 is past int32
    ang = positions[..., None].astype(F32) * inv_freq
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(p, u, mask, positions, d, fault):
    b, t, _ = u.shape
    heads, kv_heads, head_dim = d["num_attention_heads"], d["num_key_value_heads"], d["head_dim"]
    q = (u @ p["q_proj"]["kernel"]).reshape(b, t, heads, head_dim)
    k = u @ p["k_proj"]["kernel"]
    if fault != "no_key_multiplier":
        k = k * d["key_multiplier"]
    k = k.reshape(b, t, kv_heads, head_dim)
    v = (u @ p["v_proj"]["kernel"]).reshape(b, t, kv_heads, head_dim)
    if fault != "no_rotary":
        q, k = _rotary(q, positions, d["rope_theta"]), _rotary(k, positions, d["rope_theta"])
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(head_dim)
    qi, ki = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    visible = (ki < qi) if fault == "strict_causal" else (ki <= qi)
    visible = visible[None, None] & (mask[:, None, None, :] > 0)
    scores = jnp.where(visible, scores, -1e30)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(b, t, heads * head_dim) @ p["o_proj"]["kernel"]


def _mixer(p, u, mask, d, fault):
    b, t, _ = u.shape
    H, P, G, N = d["mamba_n_heads"], d["mamba_d_head"], d["mamba_n_groups"], d["mamba_d_state"]
    d_ssm, gn = H * P, G * N
    real = mask[:, :, None].astype(F32)
    proj = ((u * real) * d["ssm_in_multiplier"]) @ p["in_proj"]["kernel"]
    if fault != "no_ssm_multipliers":
        m = d["ssm_multipliers"]
        proj = proj * jnp.concatenate([jnp.full((n,), m[i], F32)
                                       for i, n in enumerate((d_ssm, d_ssm, gn, gn, H))])
    z, xbc, dt = proj[..., :d_ssm], proj[..., d_ssm : 2 * d_ssm + 2 * gn], proj[..., 2 * d_ssm + 2 * gn :]

    w = p["conv_weight"]  # [K, C], the last row is the current token's tap
    taps = w.shape[0]
    conv = xbc * w[taps - 1]
    if fault != "no_conv":
        for back in range(1, taps):  # the token `back` places to the left
            shifted = jnp.pad(xbc, ((0, 0), (back, 0), (0, 0)))[:, :t]
            conv = conv + shifted * w[taps - 1 - back]
    xbc = jax.nn.silu(conv + p["conv_bias"]) * real
    x = xbc[..., :d_ssm].reshape(b, t, H, P)
    Bm = jnp.repeat(xbc[..., d_ssm : d_ssm + gn].reshape(b, t, G, N), H // G, axis=2)
    Cm = jnp.repeat(xbc[..., d_ssm + gn :].reshape(b, t, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # [B, T, H]
    A = -jnp.exp(p["A_log"])

    def token(S, inp):
        x_t, b_t, c_t, dt_t, slot = inp
        if fault == "state_reset_per_chunk":
            S = jnp.where(slot % d["mamba_chunk_size"] == 0, 0.0, S)
        S = jnp.exp(dt_t * A)[:, :, None, None] * S + (
            (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, c_t) + p["D"][:, None] * x_t

    over_t = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(token, jnp.zeros((b, H, P, N), F32),
                        (over_t(x), over_t(Bm), over_t(Cm), over_t(dt), jnp.arange(t)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, t, d_ssm) * jax.nn.silu(z)
    y = y.reshape(b, t, G, d_ssm // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + d["rms_norm_eps"])
    return (y.reshape(b, t, d_ssm) * p["norm_scale"]) @ p["out_proj"]["kernel"]


@functools.partial(jax.jit, static_argnames=("dims", "fault"))
def _layer(layer, x, mask, positions, *, dims, fault=None):
    d = dict(zip(LAYER_DIMS, dims))
    with jax.default_matmul_precision("highest"):
        p = _up(layer, fault)
        eps = d["rms_norm_eps"]
        u = _rms_norm(x, p["ln_attn"]["scale"], eps)
        attn = d["attention_out_multiplier"] * _attention(
            p["attn"], u * d["attention_in_multiplier"], mask, positions, d, fault)
        if fault == "mixer_after_attention":
            x = x + attn
            x = x + d["ssm_out_multiplier"] * _mixer(
                p["mixer"], _rms_norm(x, p["ln_attn"]["scale"], eps), mask, d, fault)
        else:
            x = x + d["ssm_out_multiplier"] * _mixer(p["mixer"], u, mask, d, fault) + attn
        h = _rms_norm(x, p["ln_mlp"]["scale"], eps)
        gate = (h @ p["mlp"]["gate_proj"]["kernel"]) * d["mlp_multipliers"][0]
        inner = jax.nn.silu(gate) * (h @ p["mlp"]["up_proj"]["kernel"])
        return x + (inner @ p["mlp"]["down_proj"]["kernel"]) * d["mlp_multipliers"][1]


@functools.partial(jax.jit, static_argnames=("eps", "multiplier", "fault"))
def _head(ln_f, lm_head, x, *, eps, multiplier, fault=None):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, jnp.asarray(ln_f["scale"], F32), eps)
        return (h @ _up(lm_head, fault)["kernel"]) * multiplier


def _static(value):
    return tuple(float(v) for v in value) if isinstance(value, (list, tuple)) else value


def hidden(params, dims, input_ids, attention_mask, fault=None):
    """The residual stream ``[B, T, hidden]`` after the last layer, float32."""
    mask = jnp.asarray(attention_mask, jnp.int32)
    positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    x = _up(params["wte"], fault)["embedding"][jnp.asarray(input_ids)]
    x = x * float(dims["embedding_multiplier"])
    layer_dims = tuple(_static(dims[k]) for k in LAYER_DIMS)
    for i in range(int(dims["num_hidden_layers"])):
        x = _layer(params[f"h_{i}"], x, mask, positions, dims=layer_dims, fault=fault)
    return x


def logits(params, dims, input_ids, attention_mask, span, fault=None):
    """Float32 logits ``[B, span[1] - span[0], vocab]`` of the backbone tree
    ``params`` on ``input_ids`` [B, T] with ``attention_mask`` [B, T]."""
    x = hidden(params, dims, input_ids, attention_mask, fault)
    return _head(params["ln_f"], params["lm_head"], x[:, span[0] : span[1]],
                 eps=float(dims["rms_norm_eps"]), multiplier=float(dims["lm_head_multiplier"]),
                 fault=fault)
