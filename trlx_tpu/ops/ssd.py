"""The linear recurrence of a state-space or linear-attention head in plain
``jax.numpy`` (state-space duality, Dao & Gu 2024, arXiv:2405.21060): the
chunked scan the prefill, the scoring forward and the train step run, the
one-token step the decode loop runs, and Mamba-2's causal depthwise conv.

Per head ``h`` with state ``S [P, N]`` (``P`` channels of the head, ``N`` the
state size), decay rate ``A_h < 0`` and the step size ``dt_t > 0``::

    S_t = exp(dt_t A_h) S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t + D_h x_t

Two mixers run it (``models/transformer.py``). **Mamba-2** (``Mamba2Mixer``):
``dt`` a softplus of the token, ``B`` and ``C`` shared by the ``H / G`` heads
of a group, a skip ``D``. **Lightning attention** (``LightningMixer``; Qin et
al., arXiv:2401.04658: ``S_t = lambda_h S_{t-1} + k_t v_t^T``, ``o_t = S_t^T
q_t``): ``x = v``, ``B = k``, ``C = q``, ``A_h = log lambda_h``, a step of 1
on every token (``dt`` of ``[1, T, H]`` ones: whatever depends on the steps
alone, the decays inside a chunk, is then built once and not once a row),
``G = H`` and no skip (``D`` None).

What it does NOT compute: a recurrence whose update is not a plain decay by
a scalar a head a token. A delta rule (the state corrected by what it already
answers for the key) under a decay a channel is ``ops/delta_rule.py``
(``kda_chunked``, ``kda_step``: Kimi Delta Attention), which shares only
``causal_conv`` with this file.

Everything is XLA: no Pallas kernel. The decay arithmetic and every
accumulation are float32; the matmul operands keep the dtype of ``x`` (bf16
where the model computes in bf16); the state is float32 always.
"""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _per_head(t: jax.Array, heads: int) -> jax.Array:
    """``[..., G, N]`` group tensors seen by each of ``heads`` heads."""
    return jnp.repeat(t, heads // t.shape[-2], axis=-2)


def ssd_chunked(
    x: jax.Array,  # [B, T, H, P]
    dt: jax.Array,  # [B | 1, T, H] step sizes (after softplus; 1: every row's alike)
    A: jax.Array,  # [H] negative decay rates
    B: jax.Array,  # [B, T, G, N]
    C: jax.Array,  # [B, T, G, N]
    D: Optional[jax.Array],  # [H] skip; None = none
    mask: Optional[jax.Array] = None,  # [B, T] 1 on real tokens
    initial_state: Optional[jax.Array] = None,  # [B, H, P, N] float32
    chunk: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    """``(y [B, T, H, P], final_state [B, H, P, N] float32)`` of the
    recurrence above over ``T`` tokens, in chunks of ``chunk``: the quadratic
    term inside each chunk, each chunk's own state, the recurrence over
    chunks, and the carried state's part of each output (the paper's four
    steps). ``T`` is padded to the chunk inside, with ``dt = 0`` (no decay,
    no input), so ``final_state`` is the state after token ``T - 1``. A
    masked position feeds nothing into the state; it still decays it."""
    with jax.named_scope("ssm/scan"):
        Bsz, T, H, P = x.shape
        G, N = B.shape[-2], B.shape[-1]
        dtype = x.dtype
        if mask is not None:
            x = x * mask[:, :, None, None].astype(dtype)
        Q = min(chunk, T)
        pad = -T % Q
        if pad:
            x, dt, B, C = (
                jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                for t in (x, dt, B, C)
            )
        nc = (T + pad) // Q
        xc = x.reshape(Bsz, nc, Q, H, P)
        Bc = B.reshape(Bsz, nc, Q, G, N)
        Cc = C.reshape(Bsz, nc, Q, G, N)
        dtc = dt.astype(F32).reshape(dt.shape[0], nc, Q, H)
        # log-decay accumulated inside each chunk: cs[t] = sum_{s<=t} dt_s A,
        # heads before positions so that the chunk is the minor dimension
        cs = jnp.cumsum(dtc * A.astype(F32), axis=2).transpose(0, 1, 3, 2)  # [B, nc, H, Q]
        xdt = (xc.astype(F32) * dtc[..., None]).astype(dtype)

        # 1. inside a chunk: y_t += sum_{s<=t} (C_t . B_s) exp(cs_t - cs_s) dt_s x_s
        cb = jnp.einsum("bcqgn,bcsgn->bcgqs", Cc, Bc, preferred_element_type=F32)
        seg = cs[..., :, None] - cs[..., None, :]  # [B, nc, H, Q(t), Q(s)]
        causal = jnp.tril(jnp.ones((Q, Q), bool))
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))  # 0 above the diagonal
        scores = jnp.repeat(cb, H // G, axis=2) * decay
        y = jnp.einsum("bchqs,bcshp->bcqhp", scores.astype(dtype), xdt,
                       preferred_element_type=F32)

        # 2. each chunk's own state: sum_s exp(cs_last - cs_s) dt_s x_s (outer) B_s
        to_end = jnp.exp(cs[..., -1:] - cs).transpose(0, 1, 3, 2)  # [B, nc, Q, H]
        xw = (xdt.astype(F32) * to_end[..., None]).astype(dtype)
        states = jnp.einsum("bcqgrp,bcqgn->bcgrpn", xw.reshape(Bsz, nc, Q, G, H // G, P), Bc,
                            preferred_element_type=F32).reshape(Bsz, nc, H, P, N)

        # 3. the recurrence over chunks, in float32
        chunk_decay = jnp.exp(cs[..., -1])  # [B, nc, H]
        s0 = (jnp.zeros((Bsz, H, P, N), F32) if initial_state is None
              else initial_state.astype(F32))

        def carry_over(s, inp):
            a, own = inp
            return s * a[:, :, None, None] + own, s

        final, entering = jax.lax.scan(
            carry_over, s0, (chunk_decay.swapaxes(0, 1), states.swapaxes(0, 1))
        )
        entering = entering.swapaxes(0, 1)  # [B, nc, H, P, N]: state at chunk start

        # 4. the carried state's part: y_t += exp(cs_t) C_t . S_in
        y_off = jnp.einsum("bcqgn,bcgrpn->bcqgrp", Cc,
                           entering.astype(dtype).reshape(Bsz, nc, G, H // G, P, N),
                           preferred_element_type=F32).reshape(Bsz, nc, Q, H, P)
        y = y + y_off * jnp.exp(cs).transpose(0, 1, 3, 2)[..., None]
        if D is not None:
            y = y + xc.astype(F32) * D.astype(F32)[:, None]
        return y.reshape(Bsz, nc * Q, H, P)[:, :T].astype(dtype), final


def ssd_step(
    state: jax.Array,  # [B, H, P, N] float32
    x: jax.Array,  # [B, H, P]
    dt: jax.Array,  # [B | 1, H]
    A: jax.Array,  # [H]
    B: jax.Array,  # [B, G, N]
    C: jax.Array,  # [B, G, N]
    D: Optional[jax.Array],  # [H]; None = no skip
) -> Tuple[jax.Array, jax.Array]:
    """One token of the recurrence: ``(y [B, H, P], new state)``, float32
    throughout (the step is bound by reading and writing the state)."""
    with jax.named_scope("ssm/step"):
        H = x.shape[1]
        dt, xf = dt.astype(F32), x.astype(F32)
        Bh, Ch = _per_head(B.astype(F32), H), _per_head(C.astype(F32), H)
        decay = jnp.exp(dt * A.astype(F32))  # [B, H]
        state = state * decay[:, :, None, None] + (
            (dt[..., None] * xf)[..., None] * Bh[:, :, None, :]
        )
        y = jnp.einsum("bhpn,bhn->bhp", state, Ch)
        if D is not None:
            y = y + D.astype(F32)[:, None] * xf
        return y.astype(x.dtype), state


def causal_conv(
    x: jax.Array,  # [B, T, C]
    weight: jax.Array,  # [K, C] depthwise taps, oldest first
    bias: Optional[jax.Array],  # [C]; None = no bias
    conv_state: Optional[jax.Array] = None,  # [B, K - 1, C] rows before x
) -> Tuple[jax.Array, jax.Array]:
    """``y_t = bias + sum_k weight[k] * x_{t - (K-1) + k}`` over the channel's
    own past, with ``conv_state`` (zeros if None) as the ``K - 1`` rows to the
    left of ``x``. Returns ``(y [B, T, C], new conv_state)``: the last
    ``K - 1`` rows of what the conv has seen, before the conv."""
    with jax.named_scope("ssm/conv"):
        Bsz, T, Cn = x.shape
        K = weight.shape[0]
        if conv_state is None:
            conv_state = jnp.zeros((Bsz, K - 1, Cn), x.dtype)
        seen = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)  # [B, K-1+T, C]
        y = seen[:, :T] * weight[0].astype(x.dtype) if bias is None else bias.astype(x.dtype)
        for k in range(bias is None, K):
            y = y + seen[:, k : k + T] * weight[k].astype(x.dtype)
        return y, seen[:, T:]
