"""Fused flash attention as a Pallas TPU kernel.

This replaces the materialised ``[B, H, T, S]`` score tensor of the naive XLA
path (``trlx_tpu/models/transformer.py``) for the two training-dominant passes
identified in SURVEY.md §3 — the rollout scoring forward and the train-step
forward/backward. (Single-token decode keeps the einsum path: its score tensor
is ``[B, H, 1, S]`` and HBM-bound either way.) The reference gets the same op
from CUDA fused attention inside HF transformers (SURVEY.md §2.4); here it is
a TPU kernel with an online-softmax forward and a recomputation backward wired
up via ``jax.custom_vjp``.

Design notes:
- Masking is *slot-causal + key-validity*, matching
  ``CausalTransformer._attention_bias``: key slot ``s`` is visible to query
  slot ``t`` iff ``s + k_offset <= t + q_offset`` (when causal) and
  ``key_mask[b, s] > 0``. Offsets make the same kernel serve ring attention
  (``trlx_tpu/parallel/ring_attention.py``), where each device holds one
  rotating chunk of K/V with a different global slot offset.
- ALiBi (BLOOM) is applied in-kernel from per-slot *token positions* (cumsum
  of the mask, computed by the caller) so left-padded prompts get correct
  relative distances.
- The forward also emits the per-row logsumexp ``L``; ``(out, L)`` pairs
  combine associatively, which is exactly what the ring-attention accumulator
  needs.
- float32 throughout; inputs may be bf16. Every q, k, v, do tile is
  converted to float32 in VMEM (q scaled by ``sm_scale`` once), every
  contraction takes float32 operands at the default precision, and the
  softmax, ``lse``, ``delta``, ``p``, ``ds``, the output accumulator and the
  ``dq`` / ``dk`` / ``dv`` partials are float32. Mosaic serves a float32
  contraction at the default precision in ONE bf16 pass on a v5e, so
  handing it bf16 operands (and rounding ``p`` and ``ds`` to bf16 for it)
  buys nothing and costs 5 to 10% at equal tiles (PERF.md section 6, PR 34).
- Tiles come from the shapes (``choose_blocks``): a row of up to 896 slots
  is one tile, a longer one walks 512 x 512 tiles; an explicit ``block_q`` /
  ``block_k`` is honoured (tests, ring attention). The tile loop runs the
  body without positional masks over the tiles that lie wholly below the
  diagonal and inside the window (``_fwd_tile_bounds``); only the diagonal's
  and the window's edge tiles build their ``iota`` compares.
- A head's q and k may be one size (``D``) and its v another (``Dv``, the
  last axis of ``v``, ``o`` and ``do``): latent attention's 192 beside 128.
  Nothing is padded to the larger; with ``Dv = D`` the programs are the
  ones they were.
- Registered in ``analysis/kernels.py::KERNEL_PARITY`` as ``flash-fwd`` /
  ``flash-bwd``: graftlint's kernel-discipline pass (GL1001–GL1004) keeps
  both entries gated through ``pallas_utils``, the kernel bodies pure, and
  the ``attention_reference`` parity pin alive (docs/STATIC_ANALYSIS.md).
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from trlx_tpu.ops.pallas_utils import (  # noqa: F401  (NEG_INF/LANES re-export)
    LANES,
    NEG_INF,
    pad_to as _pad_to,
    resolve_interpret as _resolve_interpret,
    smem_spec as _smem_spec,
)

# The kernels' names in a device trace: ``pallas_call(name=...)`` wraps the
# call in a named scope, and XLA names the custom call after the innermost
# scope, so the two events read ``%flash_attention_fwd.N = ...`` and
# ``%flash_attention_bwd.N = ...`` on the chip's ``XLA Ops`` line (without a
# name both took the enclosing flax scope's, ``%attn.N``). The benchmark's
# per-kernel metrics match on these strings.
FWD_KERNEL_NAME = "flash_attention_fwd"
BWD_KERNEL_NAME = "flash_attention_bwd"


# ---------------------------------------------------------------------------
# the tile walk
# ---------------------------------------------------------------------------


def _clip(x, lo, hi):
    return min(max(x, lo), hi)


def _fwd_tile_bounds(q0, koff, block_q, block_k, n_k, causal, window, clip=_clip):
    """``(lo, lo_in, hi_in, hi)``: the key blocks a query block that starts
    at slot ``q0`` walks are ``[lo, hi)``, and those of ``[lo_in, hi_in)``
    need no positional mask: wholly at or below the diagonal and wholly
    inside every query's window. The kernel calls it on traced scalars
    (``clip=jnp.clip``), ``block_pairs_visited`` on host integers; ``//``
    floors in both."""
    if causal:
        # last k block whose first slot can be visible to any query in this
        # q block: k_slot <= q_slot  ⇔  koff + s <= q0 + bQ - 1
        hi = clip((q0 + block_q - koff + block_k - 1) // block_k, 0, n_k)
        # wholly at or below the diagonal: koff + (ik+1)*bK - 1 <= q0
        hi_in = clip((q0 - koff + 1) // block_k, 0, hi)
    else:
        hi = hi_in = n_k
    lo = lo_in = 0
    if window:
        # first k block any query here can see: k_slot > q_slot - window
        lo = clip((q0 - (window - 1) - koff) // block_k, 0, hi)
        # wholly inside the window: q0 + bQ - 1 - (koff + ik*bK) < window
        lo_in = clip((q0 + block_q - window - koff + block_k - 1) // block_k, lo, hi_in)
    return lo, lo_in, hi_in, hi


def _bwd_tile_bounds(k0, qoff, block_q, block_k, n_q, causal, window, clip=_clip):
    """``(lo, lo_in, hi_in, hi)`` of the query blocks the backward walks for
    the key block that starts at slot ``k0``: the transpose of
    :func:`_fwd_tile_bounds`."""
    if causal:
        lo = clip((k0 - qoff) // block_q, 0, n_q)
        # wholly at or below the diagonal: qoff + iq*bQ >= k0 + bK - 1
        lo_in = clip((k0 + block_k - 1 - qoff + block_q - 1) // block_q, lo, n_q)
    else:
        lo = lo_in = 0
    hi = hi_in = n_q
    if window:
        # last q block that can still see this k block: q_slot < k_slot + W
        hi = clip((k0 + block_k + window - 2 - qoff) // block_q + 1, lo, n_q)
        lo_in = clip(lo_in, lo, hi)
        # every query has the whole k block in its window:
        # qoff + (iq+1)*bQ - 1 - k0 < window
        hi_in = clip((k0 + window - qoff) // block_q, lo_in, hi)
    return lo, lo_in, hi_in, hi


def _walk_tiles(tile, bounds, carry, positional: bool):
    """``tile(i, carry, positional)`` over ``[lo, hi)`` in ascending order,
    so the sums run in the order they always did: the body with positional
    masks on the edge ranges, without them on ``[lo_in, hi_in)``."""
    lo, lo_in, hi_in, hi = bounds
    edge = functools.partial(tile, positional=positional)
    interior = functools.partial(tile, positional=False)
    carry = jax.lax.fori_loop(lo, lo_in, edge, carry)
    carry = jax.lax.fori_loop(lo_in, hi_in, interior, carry)
    return jax.lax.fori_loop(hi_in, hi, edge, carry)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    qoff_ref,  # SMEM (1,)
    koff_ref,  # SMEM (1,)
    q_ref,  # (1, 1, bQ, D)
    k_ref,  # (1, 1, Sp, D)
    v_ref,  # (1, 1, Sp, Dv)
    kmask_ref,  # (1, 1, Sp)
    qpos_ref,  # (1, 1, bQ)
    kpos_ref,  # (1, 1, Sp)
    slopes_ref,  # SMEM (H,) alibi slopes
    o_ref,  # (1, 1, bQ, Dv)
    l_ref,  # (1, 1, bQ, LANES) lane-replicated logsumexp
    *,
    sm_scale: float,
    causal: bool,
    alibi: bool,
    block_k: int,
    seq_k: int,
    block_q: int,
    window: int,  # sliding-window width in slots (0 = unbounded)
):
    iq = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # (bQ, D)
    qoff = qoff_ref[0]
    koff = koff_ref[0]
    q0 = qoff + iq * block_q  # first query slot of this block
    q_slots = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    if alibi:
        q_pos = qpos_ref[0, 0].astype(jnp.float32).reshape(block_q, 1)
        slope = slopes_ref[pl.program_id(1)]

    bounds = _fwd_tile_bounds(
        q0, koff, block_q, block_k, seq_k // block_k, causal, window, jnp.clip
    )

    def tile(ik, carry, positional):
        acc, m, l = carry
        k = k_ref[0, 0, pl.ds(ik * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(ik * block_k, block_k), :].astype(jnp.float32)
        kmask = kmask_ref[0, 0, pl.ds(ik * block_k, block_k)].reshape(1, block_k)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bQ, bK)
        visible = kmask > 0.5
        if positional:
            k_slots = (
                koff
                + ik * block_k
                + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            )
            if causal:
                visible = visible & (k_slots <= q_slots)
            if window:
                # slots are laid out in temporal order with padding only on
                # the left, so slot distance ≡ position distance for real pairs
                visible = visible & (q_slots - k_slots < window)
        if alibi:
            k_pos = kpos_ref[0, 0, pl.ds(ik * block_k, block_k)].astype(
                jnp.float32
            ).reshape(1, block_k)
            s = s + slope * (k_pos - q_pos)
        s = jnp.where(visible, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        # exp(NEG_INF - m_new) underflows to 0 unless the whole row is masked
        # (m_new == NEG_INF); the explicit `visible` factor covers that case.
        p = jnp.exp(s - m_new) * visible.astype(jnp.float32)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc = acc * alpha + pv
        return acc, m_new, l

    acc = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)  # v's own head size
    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc, m, l = _walk_tiles(tile, bounds, (acc, m, l), causal or bool(window))

    safe_l = jnp.where(l > 0.0, l, 1.0)
    o_ref[0, 0] = (acc / safe_l).astype(o_ref.dtype)
    logsum = jnp.where(l > 0.0, m + jnp.log(safe_l), NEG_INF)
    l_ref[0, 0] = jnp.broadcast_to(logsum, (block_q, LANES))


# ---------------------------------------------------------------------------
# backward (fused: dq + dk + dv in one kernel)
# ---------------------------------------------------------------------------


def _bwd_fused_kernel(
    qoff_ref,
    koff_ref,
    q_ref,  # (1, 1, Tp, D)  full queries
    k_ref,  # (1, 1, bK, D)
    v_ref,  # (1, 1, bK, Dv)
    kmask_ref,  # (1, 1, bK)
    qpos_ref,  # (1, 1, Tp)
    kpos_ref,  # (1, 1, bK)
    slopes_ref,
    lse_ref,  # (1, 1, Tp, LANES)
    delta_ref,  # (1, 1, Tp, LANES)
    do_ref,  # (1, 1, Tp, Dv)
    dq_ref,  # (1, 1, Tp, D) f32, accumulated across the k-block grid dim
    dk_ref,  # (1, 1, bK, D)
    dv_ref,  # (1, 1, bK, Dv)
    *,
    sm_scale: float,
    causal: bool,
    alibi: bool,
    block_q: int,
    seq_q: int,
    block_k: int,
    window: int,  # sliding-window width in slots (0 = unbounded)
):
    """Fused backward: one pass over (k-block × q-blocks) produces dk/dv for
    the k block AND accumulates dq into its full-sequence buffer — the TPU
    grid is sequential per (b, h), so the dq window persists in VMEM across
    k-block steps. Versus the split dq/dkv kernels this computes the s / p /
    dp matmul chain once instead of twice (5 MXU ops per tile pair vs 7)."""
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    k = k_ref[0, 0].astype(jnp.float32)  # (bK, D)
    v = v_ref[0, 0].astype(jnp.float32)
    kmask = kmask_ref[0, 0].reshape(1, block_k)
    qoff = qoff_ref[0]
    koff = koff_ref[0]
    k0 = koff + ik * block_k  # first key slot of this block
    k_slots = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    if alibi:
        k_pos = kpos_ref[0, 0].astype(jnp.float32).reshape(1, block_k)
        slope = slopes_ref[pl.program_id(1)]

    bounds = _bwd_tile_bounds(
        k0, qoff, block_q, block_k, seq_q // block_q, causal, window, jnp.clip
    )

    def tile(iq, carry, positional):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(iq * block_q, block_q), :].astype(jnp.float32) * sm_scale
        do = do_ref[0, 0, pl.ds(iq * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(iq * block_q, block_q), 0:1]
        delta = delta_ref[0, 0, pl.ds(iq * block_q, block_q), 0:1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        visible = kmask > 0.5
        if positional:
            q_slots = qoff + iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            if causal:
                visible = visible & (k_slots <= q_slots)
            if window:
                visible = visible & (q_slots - k_slots < window)
        if alibi:
            q_pos = qpos_ref[0, 0, pl.ds(iq * block_q, block_q)].astype(
                jnp.float32
            ).reshape(block_q, 1)
            s = s + slope * (k_pos - q_pos)
        p = jnp.exp(jnp.where(visible, s, NEG_INF) - lse) * visible.astype(
            jnp.float32
        )
        dv_blk = jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bK, D)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        dk_blk = jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bK, D)
        dq_blk = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bQ, D)
        cur = dq_ref[0, 0, pl.ds(iq * block_q, block_q), :]
        dq_ref[0, 0, pl.ds(iq * block_q, block_q), :] = cur + dq_blk * sm_scale
        return dk + dk_blk, dv + dv_blk

    dk = jnp.zeros((block_k, k_ref.shape[-1]), jnp.float32)
    dv = jnp.zeros((block_k, v_ref.shape[-1]), jnp.float32)
    dk, dv = _walk_tiles(tile, bounds, (dk, dv), causal or bool(window))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# host-side wrappers
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11, 12, 13, 14)
)
def _flash(
    q,  # (B, H, T, D)
    k,  # (B, H, S, D)
    v,  # (B, H, S, D)
    kmask,  # (B, 1, S) float
    qpos,  # (B, 1, T) int32
    kpos,  # (B, 1, S) int32
    slopes,  # (H,) float32 (zeros when alibi disabled)
    offsets,  # (q_offset, k_offset) int32 arrays of shape (1,)
    sm_scale: float,
    causal: bool,
    alibi: bool,
    block_q: int,
    block_k: int,
    interpret: bool,
    window: int,
):
    out, _ = _flash_fwd_impl(
        q, k, v, kmask, qpos, kpos, slopes, offsets,
        sm_scale, causal, alibi, block_q, block_k, interpret, window,
    )
    return out


def _flash_fwd_impl(
    q, k, v, kmask, qpos, kpos, slopes, offsets,
    sm_scale, causal, alibi, block_q, block_k, interpret, window=0,
):
    B, H, T, D = q.shape
    KV, S, Dv = k.shape[1], k.shape[2], v.shape[3]
    group = H // KV  # grouped-query attention: q-head h reads kv-head h//group
    qoff, koff = offsets
    grid = (B, H, T // block_q)

    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale,
        causal=causal,
        alibi=alibi,
        block_k=block_k,
        seq_k=S,
        block_q=block_q,
        window=window,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _smem_spec(),
            _smem_spec(),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, S, Dv), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, S), lambda b, h, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, i: (b, 0, i)),
            pl.BlockSpec((1, 1, S), lambda b, h, i: (b, 0, 0)),
            _smem_spec(),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, Dv), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, LANES), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, T, LANES), jnp.float32),
        ],
        interpret=interpret,
        name=FWD_KERNEL_NAME,
        **_fwd_vmem_params(S, D, q.dtype.itemsize, block_q, block_k, interpret, Dv),
    )(qoff, koff, q, k, v, kmask, qpos, kpos, slopes)
    return out, lse


def _flash_fwd_rule(
    q, k, v, kmask, qpos, kpos, slopes, offsets,
    sm_scale, causal, alibi, block_q, block_k, interpret, window,
):
    out, lse = _flash_fwd_impl(
        q, k, v, kmask, qpos, kpos, slopes, offsets,
        sm_scale, causal, alibi, block_q, block_k, interpret, window,
    )
    res = (q, k, v, kmask, qpos, kpos, slopes, offsets, out, lse)
    return out, res


def _bwd_fused_call(
    qoff, koff, q, k, v, kmask, qpos, kpos, slopes, lse, delta, do,
    sm_scale, causal, alibi, block_q, block_k, interpret, window=0,
):
    """Single fused pallas call producing (dq, dk, dv) on kernel-layout
    padded inputs. dq accumulates in f32 across the sequential k-block grid
    (``sm_scale`` applied in-kernel); GQA partials are group-summed here."""
    B, H, T, D = q.shape
    KV, S, Dv = k.shape[1], k.shape[2], v.shape[3]
    group = H // KV
    kernel = functools.partial(
        _bwd_fused_kernel,
        sm_scale=sm_scale,
        causal=causal,
        alibi=alibi,
        block_q=block_q,
        seq_q=T,
        block_k=block_k,
        window=window,
    )
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(B, H, S // block_k),
        in_specs=[
            _smem_spec(),
            _smem_spec(),
            pl.BlockSpec((1, 1, T, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i: (b, h // group, i, 0)),
            pl.BlockSpec((1, 1, block_k, Dv), lambda b, h, i: (b, h // group, i, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, i: (b, 0, i)),
            pl.BlockSpec((1, 1, T), lambda b, h, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, i: (b, 0, i)),
            _smem_spec(),
            pl.BlockSpec((1, 1, T, LANES), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, T, LANES), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, T, Dv), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, T, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, Dv), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, S, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, S, Dv), v.dtype),
        ],
        interpret=interpret,
        name=BWD_KERNEL_NAME,
        **_bwd_vmem_params(T, D, q.dtype.itemsize, block_q, block_k, interpret, Dv),
    )(qoff, koff, q, k, v, kmask, qpos, kpos, slopes, lse, delta, do)
    if group > 1:
        dk = dk.reshape(B, KV, group, S, D).sum(axis=2)
        dv = dv.reshape(B, KV, group, S, Dv).sum(axis=2)
    return dq.astype(q.dtype), dk, dv


# Mosaic's default scoped VMEM
_SCOPED_VMEM_BYTES = 16 * 2**20


def _tile_working_bytes(block_q: int, block_k: int, D: int, itemsize: int) -> int:
    """What one tile pair keeps live beside the whole-sequence operands: six
    ``(block_q, block_k)`` float32 intermediates (scores, mask, p, dp, ds and
    one to spare), and the double-buffered tile operands with their float32
    copies and partials of ``D`` columns on either side."""
    return 6 * block_q * block_k * 4 + 4 * (block_q + block_k) * D * (itemsize + 4)


def _vmem_params(resident: int, working: int, interpret: bool) -> dict:
    """``pallas_call`` keywords: nothing (the program every sequence up to a
    few thousand slots has always had) unless the operands a kernel keeps in
    VMEM across its grid steps and its tile's working set outgrow the default
    scope; then the kernel asks for both of a v5e's 128 MiB."""
    if interpret or resident + working <= _SCOPED_VMEM_BYTES:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    limit = resident + 2 * working
    return {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=limit)}


def _fwd_vmem_params(S: int, D: int, itemsize: int, block_q: int, block_k: int, interpret: bool, Dv: Optional[int] = None) -> dict:
    """The forward keeps a head's whole K (``S x D``) and V (``S x Dv``) and
    the key mask and positions (float32 and int32 rows, padded to eight
    sublanes), each double-buffered: 8.5 MiB at 8192 slots and head size 128."""
    Dv = Dv or D
    resident = 2 * S * ((D + Dv) * itemsize + 2 * 8 * 4)
    return _vmem_params(resident, _tile_working_bytes(block_q, block_k, max(D, Dv), itemsize), interpret)


def _bwd_vmem_params(T: int, D: int, itemsize: int, block_q: int, block_k: int, interpret: bool, Dv: Optional[int] = None) -> dict:
    """The fused backward keeps whole-sequence operands in VMEM across the
    k-block steps, each double-buffered: q (``T x D``) and do (``T x Dv``),
    dq (float32) and lse and delta (float32, ``LANES`` padded to a 128-lane
    tile): 32 MiB at 8192 slots and head size 128."""
    Dv = Dv or D
    resident = 2 * T * ((D + Dv) * itemsize + D * 4 + 2 * 128 * 4)
    return _vmem_params(resident, _tile_working_bytes(block_q, block_k, max(D, Dv), itemsize), interpret)


def _flash_bwd_rule(
    sm_scale, causal, alibi, block_q, block_k, interpret, window, res, do
):
    q, k, v, kmask, qpos, kpos, slopes, offsets, out, lse = res
    B, H, T, D = q.shape
    qoff, koff = offsets
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # (B, H, T)
    delta = jnp.broadcast_to(delta[..., None], (B, H, T, LANES))

    args = (qoff, koff, q, k, v, kmask, qpos, kpos, slopes, lse, delta, do)
    opts = (sm_scale, causal, alibi, block_q, block_k, interpret, window)
    dq, dk, dv = _bwd_fused_call(*args, *opts)

    zeros_like = jax.tree_util.tree_map(jnp.zeros_like, (kmask, qpos, kpos, slopes, offsets))
    return (dq, dk, dv) + zeros_like


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)

# ---------------------------------------------------------------------------
# under a selection: each query's softmax over a set of keys of its own
# ---------------------------------------------------------------------------


# a selection by blocks reaches the kernels in stretches of this many blocks: a vector register's lanes
SEL_LANES = 128


def _tile_of_blocks(blocks, first, block_k: int, sel_block: int):
    """``(bQ, bK)`` bool: a key tile's mask from a selection by blocks of
    ``sel_block`` keys. ``blocks (bQ, SEL_LANES)`` bf16 holds, for each query,
    one 0/1 a block of a 128-block stretch of the row, the tile's own
    starting at lane ``first``; column ``c`` of the tile takes lane ``first +
    c // sel_block``: one ``(bQ, 128) x (128, bK)`` product with a 0/1
    matrix built from two iotas, which the MXU has to spare (a lane gather
    or a dynamic lane slice narrower than 128 Mosaic does not lower)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (SEL_LANES, block_k), 0)
    column = jax.lax.broadcasted_iota(jnp.int32, (SEL_LANES, block_k), 1)
    spread = (lane == first + column // sel_block).astype(blocks.dtype)
    # 0/1 operands: one bf16 pass is exact, and Mosaic takes no float32 pass over bf16 operands,
    # which a process-wide `jax_default_matmul_precision: highest` would otherwise ask for
    return jax.lax.dot_general(blocks, spread, (((1,), (0,)), ((), ())), precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32) > 0.5


def _selected_fwd_kernel(
    q_ref,  # (1, 1, bQ, D)
    k_ref,  # (1, 1, Sp, D)
    v_ref,  # (1, 1, Sp, Dv)
    kmask_ref,  # (1, 1, Sp)
    sel_ref,  # (1, 1, bQ, Sp) int8: nonzero = this query keeps this key; (1, 1, bQ, NBp) bf16 by blocks of keys
    o_ref,  # (1, 1, bQ, Dv)
    l_ref,  # (1, 1, bQ, LANES)
    *,
    sm_scale: float,
    block_k: int,
    seq_k: int,
    block_q: int,
    sel_block: int,
):
    """``_fwd_kernel``'s causal walk (slot offsets 0, no window, no ALiBi)
    with one more mask a tile: the query block's rows of the selection. Every
    tile up to the diagonal is visited and masked: a learned selection keeps
    some key of nearly every tile, so there is none to skip. A selection by
    blocks of ``sel_block`` keys is widened to the tile on the MXU
    (``_tile_of_blocks``)."""
    iq = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale
    q0 = iq * block_q
    q_slots = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    hi = jnp.clip((q0 + block_q + block_k - 1) // block_k, 0, seq_k // block_k)

    def tile(ik, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.ds(ik * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(ik * block_k, block_k), :].astype(jnp.float32)
        kmask = kmask_ref[0, 0, pl.ds(ik * block_k, block_k)].reshape(1, block_k)
        if sel_block == 1:
            chosen = sel_ref[0, 0, :, pl.ds(ik * block_k, block_k)].astype(jnp.int32) != 0
        else:
            first = ik * (block_k // sel_block)  # the tile's first block of keys
            lanes = pl.multiple_of((first // SEL_LANES) * SEL_LANES, SEL_LANES)
            chosen = _tile_of_blocks(sel_ref[0, 0, :, pl.ds(lanes, SEL_LANES)], first - lanes, block_k, sel_block)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bQ, bK)
        k_slots = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        visible = (kmask > 0.5) & (k_slots <= q_slots) & chosen
        s = jnp.where(visible, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new) * visible.astype(jnp.float32)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return acc * alpha + pv, m_new, l

    acc = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)
    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, hi, tile, (acc, m, l))

    safe_l = jnp.where(l > 0.0, l, 1.0)
    o_ref[0, 0] = (acc / safe_l).astype(o_ref.dtype)
    logsum = jnp.where(l > 0.0, m + jnp.log(safe_l), NEG_INF)
    l_ref[0, 0] = jnp.broadcast_to(logsum, (block_q, LANES))


def _selected_bwd_kernel(
    q_ref,  # (1, 1, Tp, D)
    k_ref,  # (1, 1, bK, D)
    v_ref,  # (1, 1, bK, Dv)
    kmask_ref,  # (1, 1, bK)
    sel_ref,  # (1, 1, Tp, bK) int8: the key block's columns of the selection; (1, 1, Tp, 128) bf16 by blocks
    lse_ref,  # (1, 1, Tp, LANES)
    delta_ref,  # (1, 1, Tp, LANES)
    do_ref,  # (1, 1, Tp, Dv)
    dq_ref,  # (1, 1, Tp, D) f32, accumulated across the k-block grid dim
    dk_ref,  # (1, 1, bK, D)
    dv_ref,  # (1, 1, bK, Dv)
    *,
    sm_scale: float,
    block_q: int,
    seq_q: int,
    block_k: int,
    sel_block: int,
):
    """``_bwd_fused_kernel`` under the same selection: dq, dk and dv in one
    pass over the query blocks at or below the key block's diagonal."""
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    kmask = kmask_ref[0, 0].reshape(1, block_k)
    k0 = ik * block_k
    k_slots = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    n_q = seq_q // block_q
    lo = jnp.clip(k0 // block_q, 0, n_q)

    def tile(iq, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(iq * block_q, block_q), :].astype(jnp.float32) * sm_scale
        do = do_ref[0, 0, pl.ds(iq * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(iq * block_q, block_q), 0:1]
        delta = delta_ref[0, 0, pl.ds(iq * block_q, block_q), 0:1]
        if sel_block == 1:
            chosen = sel_ref[0, 0, pl.ds(iq * block_q, block_q), :].astype(jnp.int32) != 0
        else:  # the 128 blocks of keys among which this key tile's lie (the BlockSpec's window)
            first = ik * (block_k // sel_block)
            chosen = _tile_of_blocks(sel_ref[0, 0, pl.ds(iq * block_q, block_q), :], first % SEL_LANES, block_k, sel_block)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        q_slots = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        visible = (kmask > 0.5) & (k_slots <= q_slots) & chosen
        p = jnp.exp(jnp.where(visible, s, NEG_INF) - lse) * visible.astype(jnp.float32)
        dv_blk = jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        dk_blk = jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dq_blk = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        cur = dq_ref[0, 0, pl.ds(iq * block_q, block_q), :]
        dq_ref[0, 0, pl.ds(iq * block_q, block_q), :] = cur + dq_blk * sm_scale
        return dk + dk_blk, dv + dv_blk

    dk = jnp.zeros((block_k, k_ref.shape[-1]), jnp.float32)
    dv = jnp.zeros((block_k, v_ref.shape[-1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, n_q, tile, (dk, dv))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _selected(q, k, v, kmask, sel, sm_scale: float, block_q: int, block_k: int, interpret: bool, sel_block: int = 1):
    """``q (B, H, T, D)`` over ``k (B, KV, T, D)``, ``v (B, KV, T, Dv)``
    (query head ``h`` reads KV head ``h // (H / KV)``), ``kmask (B, 1, T)``
    float, all padded to the tiles: causal attention with each query's
    softmax over the keys it keeps. ``sel (B, KS, T, T)`` int8 keeps keys one
    by one (``sel_block`` 1); ``sel (B, KS, T, NBp)`` bf16 keeps them by blocks
    of ``sel_block``, ``NBp`` the row's blocks padded to whole 128-lane
    stretches. One set for the ``H / KS`` query heads of each of its ``KS``."""
    return _selected_fwd_impl(q, k, v, kmask, sel, sm_scale, block_q, block_k, interpret, sel_block)[0]


def _selected_fwd_impl(q, k, v, kmask, sel, sm_scale, block_q, block_k, interpret, sel_block=1):
    B, H, T, D = q.shape
    KV, S, Dv = k.shape[1], k.shape[2], v.shape[3]
    group, per_set = H // KV, H // sel.shape[1]
    kernel = functools.partial(
        _selected_fwd_kernel, sm_scale=sm_scale, block_k=block_k, seq_k=S, block_q=block_q, sel_block=sel_block
    )
    itemsize = q.dtype.itemsize
    resident = 2 * S * ((D + Dv) * itemsize + 8 * 4) + 2 * block_q * sel.shape[3] * sel.dtype.itemsize
    return pl.pallas_call(
        kernel,
        grid=(B, H, T // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, S, Dv), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, S), lambda b, h, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, block_q, sel.shape[3]), lambda b, h, i: (b, h // per_set, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, Dv), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, LANES), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, T, LANES), jnp.float32),
        ],
        interpret=interpret,
        name=FWD_KERNEL_NAME,
        **_vmem_params(resident, _tile_working_bytes(block_q, block_k, max(D, Dv), itemsize), interpret),
    )(q, k, v, kmask, sel)


def _selected_fwd_rule(q, k, v, kmask, sel, sm_scale, block_q, block_k, interpret, sel_block):
    out, lse = _selected_fwd_impl(q, k, v, kmask, sel, sm_scale, block_q, block_k, interpret, sel_block)
    return out, (q, k, v, kmask, sel, out, lse)


def _selected_bwd_rule(sm_scale, block_q, block_k, interpret, sel_block, res, do):
    q, k, v, kmask, sel, out, lse = res
    B, H, T, D = q.shape
    KV, S, Dv = k.shape[1], k.shape[2], v.shape[3]
    group, per_set = H // KV, H // sel.shape[1]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (B, H, T, LANES))
    kernel = functools.partial(
        _selected_bwd_kernel, sm_scale=sm_scale, block_q=block_q, seq_q=T, block_k=block_k, sel_block=sel_block
    )
    itemsize = q.dtype.itemsize
    if sel_block == 1:  # the key block's own columns
        sel_spec = pl.BlockSpec((1, 1, T, block_k), lambda b, h, i: (b, h // per_set, 0, i))
    else:  # the 128-lane stretch of blocks that holds the key block's
        tile_blocks = block_k // sel_block
        sel_spec = pl.BlockSpec((1, 1, T, SEL_LANES), lambda b, h, i: (b, h // per_set, 0, i * tile_blocks // SEL_LANES))
    resident = 2 * T * ((D + Dv) * itemsize + D * 4 + 2 * 128 * 4) + 2 * T * sel_spec.block_shape[3] * sel.dtype.itemsize
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(B, H, S // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, T, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i: (b, h // group, i, 0)),
            pl.BlockSpec((1, 1, block_k, Dv), lambda b, h, i: (b, h // group, i, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, i: (b, 0, i)),
            sel_spec,
            pl.BlockSpec((1, 1, T, LANES), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, T, LANES), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, T, Dv), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, T, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, Dv), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, S, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, S, Dv), v.dtype),
        ],
        interpret=interpret,
        name=BWD_KERNEL_NAME,
        **_vmem_params(resident, _tile_working_bytes(block_q, block_k, max(D, Dv), itemsize), interpret),
    )(q, k, v, kmask, sel, lse, delta, do)
    if group > 1:  # each query head's partials, summed over its KV head's group
        dk = dk.reshape(B, KV, group, S, D).sum(axis=2)
        dv = dv.reshape(B, KV, group, S, Dv).sum(axis=2)
    # an integer operand's cotangent; the 0/1 blocks are no function of anything differentiated
    no_gradient = np.zeros(sel.shape, jax.dtypes.float0) if sel_block == 1 else jnp.zeros_like(sel)
    return dq.astype(q.dtype), dk, dv, jnp.zeros_like(kmask), no_gradient


_selected.defvjp(_selected_fwd_rule, _selected_bwd_rule)


def flash_attention_bwd_chunk(
    q: jax.Array,  # (B, T, H, D) local queries
    k: jax.Array,  # (B, S, H, D) visiting key chunk
    v: jax.Array,  # (B, S, H, D)
    key_mask: jax.Array,  # (B, S)
    lse: jax.Array,  # (B, H, T) GLOBAL logsumexp of the full (ring) softmax
    delta: jax.Array,  # (B, H, T) rowsum(do * out_final)
    do: jax.Array,  # (B, T, H, D) cotangent of the final output
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    q_offset=0,
    k_offset=0,
    q_positions: Optional[jax.Array] = None,  # (B, T) for alibi
    k_positions: Optional[jax.Array] = None,  # (B, S) for alibi
    alibi_slopes: Optional[jax.Array] = None,  # (H,)
    block_q: Optional[int] = None,  # None: choose_blocks
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,  # sliding-window width (None = unbounded)
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One (q-chunk × kv-chunk) term of the flash backward, in model layout.

    With the *global* ``lse``/``delta``, summing these terms over all kv
    chunks (rotating around the ring) reproduces the exact monolithic
    backward — this is the building block of the ring-attention VJP
    (``trlx_tpu/parallel/ring_attention.py``). One fused kernel call
    produces all three grads.
    """
    interpret = _resolve_interpret(interpret)
    B, T, H, D = q.shape
    S = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    alibi = alibi_slopes is not None
    block_q, block_k = _resolve_blocks(block_q, block_k, T, S, interpret)

    qt = _pad_to(q.transpose(0, 2, 1, 3), block_q, 2)
    kt = _pad_to(k.transpose(0, 2, 1, 3), block_k, 2)
    vt = _pad_to(v.transpose(0, 2, 1, 3), block_k, 2)
    dot = _pad_to(do.transpose(0, 2, 1, 3), block_q, 2)
    Tp, Sp = qt.shape[2], kt.shape[2]
    kmask = _pad_to(key_mask.astype(jnp.float32), block_k, 1).reshape(B, 1, Sp)
    if q_positions is None:
        q_positions = jnp.zeros((B, T), jnp.int32)
    if k_positions is None:
        k_positions = jnp.zeros((B, S), jnp.int32)
    qpos = _pad_to(q_positions.astype(jnp.int32), block_q, 1).reshape(B, 1, Tp)
    kpos = _pad_to(k_positions.astype(jnp.int32), block_k, 1).reshape(B, 1, Sp)
    slopes = (
        alibi_slopes.astype(jnp.float32).reshape(H)
        if alibi
        else jnp.zeros((H,), jnp.float32)
    )
    # padded query rows: a +inf-like lse sentinel drives p = exp(s - 1e30) to
    # zero regardless of which keys the padded slots would "see" (a NEG_INF
    # sentinel would instead overflow p to inf for visible pairs)
    lse_p = _pad_to(lse, block_q, 2)
    lse_p = jnp.where(
        jnp.arange(Tp)[None, None, :] < T, lse_p, -NEG_INF
    )
    lse_p = jnp.broadcast_to(lse_p[..., None], (B, H, Tp, LANES))
    delta_p = jnp.broadcast_to(_pad_to(delta, block_q, 2)[..., None], (B, H, Tp, LANES))
    offsets = (
        jnp.asarray(q_offset, jnp.int32).reshape(1),
        jnp.asarray(k_offset, jnp.int32).reshape(1),
    )

    args = (offsets[0], offsets[1], qt, kt, vt, kmask, qpos, kpos, slopes, lse_p, delta_p, dot)
    opts = (sm_scale, causal, alibi, block_q, block_k, interpret, int(window or 0))
    dq, dk, dv = _bwd_fused_call(*args, *opts)
    return (
        dq[:, :, :T, :].transpose(0, 2, 1, 3),
        dk[:, :, :S, :].transpose(0, 2, 1, 3),
        dv[:, :, :S, :].transpose(0, 2, 1, 3),
    )


# A row of at most this many slots is ONE tile; a longer row walks tiles of
# up to ``_LONG_ROW_TILE`` slots. Set from bare-kernel timings on a TPU v5e
# (bf16 inputs, forward | backward against the 128 x 128 tiles the kernels
# always had; PERF.md section 6, PR 34): a whole-row tile gains 1.47x | 1.75x
# at 384 slots, 1.86x | 2.02x at 640 and 1.99x | 1.96x at 896, where a loop
# iteration costs more than the masked half of the square; at 1024 slots
# 512 x 512 (2.44x | 2.24x; head size 256: 1.80x | 2.04x) beats one tile by a
# sixth, and at 8192 it gives 4.60x | 3.13x (under a window of 4096 4.03x |
# 2.86x), where 256 x 256 gave little more than half of that and
# 1024 x 1024 the backward 8% more and the forward 3 to 11% less. A long row
# that 512 does not divide: 384 x 384 at 1152 slots 1.95x | 1.84x, 256 x 256
# at 1280 2.03x | 1.54x.
_ONE_TILE_SLOTS = 896
_LONG_ROW_TILE = 512


def _largest_tile(lanes: int, most: int) -> int:
    """The largest multiple of 128 up to ``most`` that divides ``lanes``."""
    return max(t for t in range(128, most + 1, 128) if lanes % t == 0)


def _row_tile(slots: int) -> int:
    """One tile for a short row; for a long one the largest tile up to
    ``_LONG_ROW_TILE`` that divides the row as rounded up to 128 slots, so a
    row is never padded further than the 128 x 128 kernels padded it (1152
    slots walk tiles of 384, 1280 of 256, not 1536 slots of 512)."""
    lanes = -(-slots // 128) * 128
    return lanes if lanes <= _ONE_TILE_SLOTS else _largest_tile(lanes, _LONG_ROW_TILE)


def choose_blocks(T: int, S: int) -> Tuple[int, int]:
    """``(block_q, block_k)`` for ``T`` query slots over ``S`` key slots: a
    function of the shapes alone, so every caller that leaves the tile to the
    kernel (the model, the step record's counters) gets the same one. Head
    size and item size do not change the choice on a v5e: 512 x 512 at head
    size 256 fits once ``_vmem_params`` has asked for it.

    A key tile never exceeds the query tile: a prefill of ``T`` tokens into
    a cache of ``S > T`` slots sees ``T`` keys, and a wider tile is mostly
    masked (128 x 640 over a 640-slot cache runs at 0.88x of 128 x 128). It
    shrinks to a divisor of the row's own tile, so K and V are padded no
    further."""
    block_q, block_k = _row_tile(T), _row_tile(S)
    if block_k > block_q:
        block_k = _largest_tile(block_k, block_q)
    return block_q, block_k


def _resolve_blocks(block_q, block_k, T, S, interpret):
    """An explicit tile is honoured; ``None`` asks :func:`choose_blocks`.
    The interpreter has no tiling constraints, so there a tile never exceeds
    the sequence (small blocks keep CPU tests fast); on hardware tiles stay
    multiples of 128 and T/S are padded up to a tile multiple, since Mosaic
    rejects sub-128 lane blocks."""
    chosen_q, chosen_k = choose_blocks(T, S)
    block_q = chosen_q if block_q is None else block_q
    block_k = chosen_k if block_k is None else block_k
    if interpret:
        block_q = min(block_q, max(T, 8))
        block_k = min(block_k, max(S, 8))
    return block_q, block_k


def block_pairs_visited(
    width: int, window: Optional[int] = None, block_q: int = 128, block_k: int = 128
) -> Tuple[int, int, int]:
    """``(visited, causal, interior)``: the (query block, key block) pairs the
    forward kernel visits in a full pass over ``width`` slots under
    ``window``, the pairs plain causal attention visits, and the visited
    pairs that run the body without positional masks: ``_fwd_kernel``'s own
    bounds at offset 0, on the host, for ``learn/attn_visited_frac`` and
    ``learn/attn_interior_frac``."""
    n_q, n_k = -(-width // block_q), -(-width // block_k)
    visited = causal = interior = 0
    for iq in range(n_q):
        lo, lo_in, hi_in, hi = _fwd_tile_bounds(
            iq * block_q, 0, block_q, block_k, n_k, True, window
        )
        visited += hi - lo
        causal += hi
        interior += hi_in - lo_in
    return visited, causal, interior


def flash_attention(
    q: jax.Array,  # (B, T, H, D)
    k: jax.Array,  # (B, S, H, D)
    v: jax.Array,  # (B, S, H, Dv): Dv may differ from D; the output is (B, T, H, Dv)
    key_mask: jax.Array,  # (B, S) 1 = valid slot
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    q_offset=0,
    k_offset=0,
    q_positions: Optional[jax.Array] = None,  # (B, T) for alibi
    k_positions: Optional[jax.Array] = None,  # (B, S) for alibi
    alibi_slopes: Optional[jax.Array] = None,  # (H,)
    block_q: Optional[int] = None,  # None: choose_blocks
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    return_lse: bool = False,
    window: Optional[int] = None,  # sliding-window width (None = unbounded)
    selection: Optional[jax.Array] = None,  # (B, T, S) nonzero = this query keeps this key
    selection_block: int = 1,  # > 1: selection (B, KS, T, ceil(S / selection_block)), by blocks of keys
):
    """Flash attention over ``[B, T, H, D]`` tensors (model layout).

    With ``selection`` each query's softmax runs over the keys it keeps,
    causal and under the key mask as always, over the row's own keys (``S =
    T``, slot offsets 0), no window, no ALiBi; kernels of their own, under the
    same names. Two forms: ``(B, T, T)``, key by key and one set for all heads
    (a learned sparse selection over MHA); with ``selection_block`` > 1 ``(B,
    KS, T, ceil(T / selection_block))``, by blocks of that many keys and one
    set for the ``H / KS`` query heads of each of ``KS`` (a block selection
    under GQA: ``KS`` the KV heads), 1 / ``selection_block`` of the bytes.

    Pads T/S up to block multiples internally; padded key slots are invisible
    (mask 0), padded query rows produce zeros and are sliced off. With
    ``return_lse`` the per-row logsumexp over *unpadded* rows is returned too
    (needed by the ring-attention combiner). NOTE: the ``return_lse`` variant
    is forward-only (no VJP is defined for the pair); ring attention defines
    its own VJP over whole ring sweeps rather than differentiating per-chunk
    (out, lse) pairs.
    """
    interpret = _resolve_interpret(interpret)
    B, T, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    if H % KV:
        raise ValueError(f"q heads {H} not a multiple of kv heads {KV}")
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    alibi = alibi_slopes is not None
    block_q, block_k = _resolve_blocks(block_q, block_k, T, S, interpret)
    if selection is not None:
        by_blocks = selection_block > 1
        if by_blocks and interpret and (block_k % selection_block or SEL_LANES % (block_k // selection_block)):
            # the interpreter's tile is the row (`_resolve_blocks`): cut it to a power of two of whole blocks
            block_q = block_k = selection_block * 2 ** int(np.log2(max(block_k // selection_block, 1)))
        shape_ok = (
            selection.ndim == 4 and selection.shape[::2] == (B, T) and H % selection.shape[1] == 0
            and selection.shape[3] == -(-S // selection_block) and block_k % selection_block == 0
            and SEL_LANES % (block_k // selection_block) == 0
        ) if by_blocks else (H == KV and selection.shape == (B, T, S))
        if not causal or alibi or window or return_lse or S != T or not shape_ok:
            raise ValueError(
                "a selection runs causal MHA over the row's own keys: no window, no ALiBi, no lse, a (B, T, T) "
                "selection; or, under selection_block, GQA under a (B, KS, T, T / selection_block) one whose blocks "
                f"divide the key tile (got {selection.shape}, block {selection_block}, for q {q.shape}, k {k.shape}, tile {block_k})"
            )
        tile = max(block_q, block_k)  # one padded length for queries and keys (the smaller tile divides it)
        pad = lambda a, axis: _pad_to(a, tile, axis)
        if by_blocks:  # the padded row's blocks, in whole 128-lane stretches so that a tile's lie inside one
            sel = pad(selection.astype(jnp.bfloat16), 2)
            sel = _pad_to(_pad_to(sel, sel.shape[2] // selection_block, 3), SEL_LANES, 3)
        else:
            sel = pad(pad(selection.astype(jnp.int8), 1), 2)[:, None]
        out = _selected(
            pad(q.transpose(0, 2, 1, 3), 2), pad(k.transpose(0, 2, 1, 3), 2), pad(v.transpose(0, 2, 1, 3), 2),
            pad(key_mask.astype(jnp.float32), 1).reshape(B, 1, -1), sel,
            sm_scale, block_q, block_k, interpret, selection_block,
        )
        return out[:, :, :T, :].transpose(0, 2, 1, 3)

    # [B, T, H, D] → [B, H, T, D]
    qt = _pad_to(q.transpose(0, 2, 1, 3), block_q, 2)
    kt = _pad_to(k.transpose(0, 2, 1, 3), block_k, 2)
    vt = _pad_to(v.transpose(0, 2, 1, 3), block_k, 2)
    Tp, Sp = qt.shape[2], kt.shape[2]

    kmask = _pad_to(key_mask.astype(jnp.float32), block_k, 1).reshape(B, 1, Sp)
    if q_positions is None:
        q_positions = jnp.zeros((B, T), jnp.int32)
    if k_positions is None:
        k_positions = jnp.zeros((B, S), jnp.int32)
    qpos = _pad_to(q_positions.astype(jnp.int32), block_q, 1).reshape(B, 1, Tp)
    kpos = _pad_to(k_positions.astype(jnp.int32), block_k, 1).reshape(B, 1, Sp)
    slopes = (
        alibi_slopes.astype(jnp.float32).reshape(H)
        if alibi
        else jnp.zeros((H,), jnp.float32)
    )
    offsets = (
        jnp.asarray(q_offset, jnp.int32).reshape(1),
        jnp.asarray(k_offset, jnp.int32).reshape(1),
    )

    win = int(window or 0)
    if return_lse:
        out, lse = _flash_fwd_impl(
            qt, kt, vt, kmask, qpos, kpos, slopes, offsets,
            sm_scale, causal, alibi, block_q, block_k, interpret, win,
        )
        return (
            out[:, :, :T, :].transpose(0, 2, 1, 3),
            lse[:, :, :T, 0],
        )
    out = _flash(
        qt, kt, vt, kmask, qpos, kpos, slopes, offsets,
        sm_scale, causal, alibi, block_q, block_k, interpret, win,
    )
    return out[:, :, :T, :].transpose(0, 2, 1, 3)


def attention_reference(
    q, k, v, key_mask, *, causal=True, sm_scale=None,
    q_offset=0, k_offset=0, q_positions=None, k_positions=None,
    alibi_slopes=None, window=None, selection=None, selection_block=1,
) -> Tuple[jax.Array, jax.Array]:
    """Naive XLA attention with identical masking semantics (test oracle).

    Returns (out, logsumexp), both f32-accumulated.
    """
    B, T, H, D = q.shape
    S = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    s = jnp.einsum(
        "bthd,bshd->bhts", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * sm_scale
    visible = key_mask[:, None, None, :] > 0.5
    q_slots = jnp.arange(T)[:, None] + jnp.asarray(q_offset)
    k_slots = jnp.arange(S)[None, :] + jnp.asarray(k_offset)
    if causal:
        visible = visible & (k_slots <= q_slots)[None, None, :, :]
    if window:
        visible = visible & (q_slots - k_slots < window)[None, None, :, :]
    if selection is not None and selection_block > 1:  # (B, KS, T, S / block): by blocks, a set a group of heads
        keys = jnp.repeat(selection != 0, selection_block, axis=3)[..., :S]
        visible = visible & jnp.repeat(keys, H // keys.shape[1], axis=1)
    elif selection is not None:  # (B, T, S): the keys each query keeps, one set for all heads
        visible = visible & (selection != 0)[:, None, :, :]
    if alibi_slopes is not None:
        dist = (
            k_positions[:, None, :] - q_positions[:, :, None]
        ).astype(jnp.float32)
        s = s + alibi_slopes.astype(jnp.float32)[None, :, None, None] * dist[:, None]
    s = jnp.where(visible, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m) * visible.astype(jnp.float32)
    l = jnp.sum(p, axis=-1, keepdims=True)
    safe_l = jnp.where(l > 0, l, 1.0)
    out = jnp.einsum("bhts,bshd->bthd", p / safe_l, v.astype(jnp.float32))
    lse = jnp.where(l > 0, m + jnp.log(safe_l), NEG_INF)[..., 0]
    return out, lse
