"""Pallas paged-*prefill* attention: prompt flash attention computed in KV
chunks, reading and writing K/V through the block table — no dense view.

PR 12 (``ops/paged_attention.py``) deleted the per-segment gather/scatter
tax from paged *decode*; every refill prefill, however, still ran
gather → dense prefill → scatter (``ops/slot_refill.py::_make_refill``) —
the last dense-view copy on the generation hot path. This kernel closes it:
the refill forward's attention reads committed prefix blocks and the
chunk's own freshly-written K/V straight from the pool (each of a row's
blocks fetched into VMEM exactly once, driven by the scalar-prefetched
block table), and the chunk's K/V is committed by the caller
(``models/transformer.py::Attention``) with drop-mode writes through the
table — no dense-view gather on entry, no scatter on exit.

Bit-parity is the contract, inherited verbatim from the decode kernel's
design rules (pinned by ``tests/test_paged_attention.py``):

1. The kernel replicates the dense einsum path's exact op sequence on the
   per-row slice: grid steps only *land* KV blocks in VMEM scratch, then
   one compute step runs ``q·k / sqrt(depth) + bias``, ``jax.nn.softmax``
   (f32) and ``p·v`` over the full ``[T, S]`` score block — the same ops
   on the same shapes the dense path runs per row. Batch-dim slicing is
   the established bit-safe decomposition; splitting the score einsum per
   KV block is NOT (degenerate dots lower differently — see the decode
   kernel's notes), so all compute waits for the assembled row.
2. Masked key slots carry the dense path's additive ``-1e9`` bias and
   underflow softmax to exactly ``0.0`` — recycled-block stale values and
   not-yet-written pool positions contribute nothing, the same convention
   every kernel in this repo pins (``ops/pallas_utils.py``).

Chunked prefill (``ops/slot_refill.py`` chunk programs,
``engine.prefill_chunk``) calls this kernel with ``T = chunk`` queries
over the FULL ``S``-wide key row, with columns ``>= end`` bias-masked: a
chunk's queries see only the committed columns ``[0, end)`` (masked
columns contribute exact zeros), while the key width — and hence the
score dots' shapes — stays identical to the monolithic pass's, so
chunked output is bit-identical to unchunked (pinned across chunk sizes
by the parity suite; truncating the key axis instead changes the dot's
lowering at some shapes — 1-ulp contraction drift).

Off-TPU the kernel runs under the Pallas interpreter (the body as ordinary
XLA ops — what the CPU tier-1 parity suite pins); on a TPU it compiles
through Mosaic or raises.

Hardware notes (TPU v5e; compiled by ``tests/test_aot_tpu.py``, run against
the reference by ``chip_smoke.py``): block fetches are
``(block_size, KV, D)`` tiles pipelined by the grid. Mosaic's matmul takes
one batch dim, so the dots run on the row with its unit batch dim dropped
(heads are the batch dim) and accumulate in f32. VMEM holds the assembled row
(``TB·block_size × KV × D``) plus the ``[T, S]`` f32 score block — bound
``T`` with ``engine.prefill_chunk`` for long prompts on chip.

Registered in ``analysis/kernels.py::KERNEL_PARITY`` as ``paged-prefill``
(the verify seam rides the same body as ``paged-verify``): graftlint's
kernel-discipline pass enforces the gate/purity/parity conventions
statically (docs/STATIC_ANALYSIS.md).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from trlx_tpu.ops.pallas_utils import (
    align_rows,
    clamp_block_table,
    dot_precision,
    pad_bias_to,
    paged_pool_grid_spec,
    resolve_interpret,
)

__all__ = [
    "paged_prefill_attention",
    "paged_prefill_attention_reference",
]


def _paged_prefill_kernel(
    tbl_ref,  # scalar-prefetch (B, TB) int32 — drives the k/v index maps
    q_ref,  # (1, T, H, D) chunk queries (rotary already applied)
    bias_ref,  # (1, HB, T, Sp) f32 additive bias (slot-causal + validity
    #   [+alibi]); HB is 1 (head-uniform mask) or H (per-head ALiBi slopes)
    k_ref,  # (1, bs, KV, D) — pool block tbl[b, j], in place
    v_ref,  # (1, bs, KV, D)
    o_ref,  # (1, T, H, D)
    k_buf_ref,  # VMEM scratch (Sa, KV, D): the row's K, assembled per block
    v_buf_ref,  # VMEM scratch (Sa, KV, D)
    *,
    seq_len: int,  # S — logical key columns visible to this chunk
    block_size: int,
    num_blocks: int,  # TB
    group: int,  # query heads per kv head (GQA)
    head_dim: int,
):
    j = pl.program_id(1)
    # assembly steps: land this block's K/V in the row's VMEM buffers; all
    # compute waits for the full row (per-block score dots split the
    # einsum's free dim, which is not bit-preserving for tiny blocks —
    # same rule as the decode kernel)
    k_buf_ref[pl.ds(j * block_size, block_size), :, :] = k_ref[0]
    v_buf_ref[pl.ds(j * block_size, block_size), :, :] = v_ref[0]

    @pl.when(j == num_blocks - 1)
    def _finish():
        # the dense path on the per-row slice, op for op: GQA repeat;
        # scores = einsum(q, k) / sqrt(depth); scores += bias;
        # probs = softmax(f32(scores)).astype(dtype); out = einsum(probs, v)
        # The row's unit batch dim is DROPPED, so heads are each dot's one
        # batch dim: Mosaic's matmul takes at most one ("Up to 1 batch dim
        # supported"). An earlier version kept it for the interpreter's
        # sake (on jax 0.4.37 dropping it shifted contraction bits by 1
        # ulp on the CPU); on the installed toolchain the parity suite is
        # bit-equal either way, and only this form lowers.
        q = q_ref[0]  # (T, H, D)
        k = k_buf_ref[0:seq_len, :, :]
        vv = v_buf_ref[0:seq_len, :, :]
        if group > 1:
            k = jnp.repeat(k, group, axis=1)
            vv = jnp.repeat(vv, group, axis=1)
        precision = dot_precision(q.dtype)
        raw = jnp.einsum(
            "thd,shd->hts", q, k,
            precision=precision, preferred_element_type=jnp.float32,
        ).astype(q.dtype)  # (H, T, S)
        scores = raw / jnp.sqrt(jnp.float32(head_dim)).astype(raw.dtype)
        # (HB, T, S) broadcasts over heads exactly like the dense path's
        # [B, HB, T, S] bias against its [B, H, T, S] scores
        bias = bias_ref[0][:, :, 0:seq_len]
        scores = scores + bias.astype(scores.dtype)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(
            raw.dtype
        )
        out = jnp.einsum(
            "hts,shd->thd", probs, vv,
            precision=precision, preferred_element_type=jnp.float32,
        )[None]  # (1, T, H, D)
        o_ref[...] = out.astype(o_ref.dtype)


def paged_prefill_attention(
    q: jax.Array,  # (B, T, H, D) chunk queries (rotary already applied)
    k_pool: jax.Array,  # (NB, bs, KV, D) — the persistent block pool
    v_pool: jax.Array,  # (NB, bs, KV, D)
    block_table: jax.Array,  # (B, TB) int32; out-of-range ids clamp (their
    #   lanes are bias-masked or belong to padding rows whose output drops)
    bias: jax.Array,  # (B, HB, T, S) additive f32 bias (0 visible / -1e9
    #   masked [+ ALiBi]); HB is 1, or H for per-head slopes
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Chunked prompt attention reading K/V through the block table.

    Returns ``(B, T, H, D)`` in ``q.dtype`` — bit-identical to gathering
    the pool into a dense ``[B, S, KV, D]`` view and running the dense
    einsum attention with the same ``bias`` (pinned by the parity suite).
    The pool is only read; the chunk's own K/V must already be committed
    through the table (``models/transformer.py`` does the one drop-mode
    write per chunk position before calling in).
    """
    B, T, H, D = q.shape
    NB, bs, KV, _ = k_pool.shape
    if H % KV:
        raise ValueError(f"q heads {H} not a multiple of kv heads {KV}")
    group = H // KV
    TB = block_table.shape[1]
    HB = bias.shape[1]
    if HB not in (1, H):
        raise ValueError(
            f"bias head dim {HB} must be 1 (head-uniform) or H={H}"
        )
    if bias.shape[2] != T:
        raise ValueError(
            f"bias query dim {bias.shape[2]} != chunk length T={T}"
        )
    S = bias.shape[3]
    if TB * bs < S:
        raise ValueError(
            f"block table covers {TB * bs} columns < bias width {S}"
        )
    interpret = resolve_interpret(interpret)
    S_pad = TB * bs
    # scratch rounded up for hardware tiling; the kernel reads [0:S] slices
    S_align = align_rows(S_pad, interpret)
    bias_p = pad_bias_to(bias, S_pad)
    tbl = clamp_block_table(block_table, NB)

    kernel = functools.partial(
        _paged_prefill_kernel,
        seq_len=S,
        block_size=bs,
        num_blocks=TB,
        group=group,
        head_dim=D,
    )
    grid_spec = paged_pool_grid_spec(
        batch=B,
        table_blocks=TB,
        block_size=bs,
        kv_heads=KV,
        head_dim=D,
        q_block=(1, T, H, D),
        bias_block=(1, HB, T, S_pad),
        out_block=(1, T, H, D),
        scratch_rows=S_align,
        k_dtype=k_pool.dtype,
        v_dtype=v_pool.dtype,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, H, D), q.dtype),
        interpret=interpret,
    )(tbl, q, bias_p, k_pool, v_pool)


def paged_prefill_attention_reference(
    q: jax.Array,  # (B, T, H, D)
    k_pool: jax.Array,  # (NB, bs, KV, D)
    v_pool: jax.Array,  # (NB, bs, KV, D)
    block_table: jax.Array,  # (B, TB)
    bias: jax.Array,  # (B, HB, T, S); HB is 1 or H (per-head ALiBi)
) -> jax.Array:
    """Gather-then-dense oracle: the exact computation the gather refill's
    dense einsum attention performs on the gathered view (the parity
    reference)."""
    B, T, H, D = q.shape
    NB, bs, KV, _ = k_pool.shape
    S = bias.shape[3]

    def view(pool):
        v = pool[jnp.minimum(block_table, NB - 1)]  # (B, TB, bs, KV, D)
        v = v.reshape(B, -1, KV, D)[:, :S]
        if KV < H:
            v = jnp.repeat(v, H // KV, axis=2)
        return v

    k, v = view(k_pool), view(v_pool)
    depth = jnp.asarray(D, q.dtype)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(depth)
    scores = scores + bias.astype(scores.dtype)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)
