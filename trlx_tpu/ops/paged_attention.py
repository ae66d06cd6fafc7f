"""In-place paged-attention decode + fused sampling as Pallas TPU kernels.

The paged Engine (``ops/paged_kv.py``, ``trlx_tpu/engine/``) fixed the
block-pool KV layout while leaving compute dense: every decode segment
gathers the pool into a transient dense ``[B, S, kvH, D]`` view, runs the
unchanged dense attention, and scatters the written span back. The kernels
here delete that per-segment gather for the decode inner loop:

- :func:`paged_attention_decode` reads K/V **through the block table in
  place**: the grid walks ``(slot, head, table entry)`` and a
  scalar-prefetched block table drives each KV block's fetch straight from
  the pool (``PrefetchScalarGridSpec`` — the index map is the block table),
  so no dense view is ever materialized and nothing is scattered back for
  attention reads. The current step's K/V is committed by the caller with a
  one-position drop-mode write (``models/transformer.py::Attention``).
- :func:`fused_sample` runs the whole temperature / top-k / top-p filter,
  the gumbel-argmax draw, and the behavior-logprob gather as one kernel per
  row, so the per-step logits never round-trip a sort through XLA HBM
  intermediates (4+ ``[B, V]`` temporaries on the XLA path).

Bit-parity is the whole contract (ISSUE 13; pinned by
``tests/test_paged_attention.py`` / ``tests/test_engine.py``): kernel-path
decode must equal the gather-path reference bit for bit, which is already
bit-identical to plain ``generate``. Two design rules follow:

1. The attention kernel replicates the dense einsum path's exact op
   sequence: the grid's block steps only *land* K/V blocks in VMEM scratch
   (the block table is pure data movement), then one compute step runs
   ``q·k / sqrt(depth) + bias``, ``jax.nn.softmax`` and ``p·v`` over the
   full row — the same ops on the same shapes the dense path runs on its
   per-row slice, consuming the same additive ``-1e9`` bias rows
   (``CausalTransformer._attention_bias``). Masked scores underflow
   ``exp`` to exactly ``0.0`` (the same convention
   ``ops/flash_attention.py`` pins with its ``NEG_INF`` sentinel), which is
   what makes recycled-block stale values contribute nothing. Two rejected
   alternatives, for the next kernel author: online softmax is
   associativity-equivalent but not bit-equivalent, and per-block score
   dots split the einsum's free dim, which changes bits for tiny blocks
   (``block_size=1`` lowers the degenerate dot differently) — decode rows
   are short enough that the full row sits in VMEM, so neither trade is
   worth breaking the contract for.
2. The sampling kernel takes its gumbel noise as an *input* generated by
   the same ``jax.random.gumbel`` calls ``jax.random.categorical`` makes
   internally (``categorical == argmax(gumbel(key) + logits)``), so the
   draw is the reference draw to the bit; the in-kernel filter is literally
   ``ops/sampling.py::process_logits`` traced into the kernel body.

Off-TPU both kernels run under the Pallas interpreter (the kernel body as
ordinary XLA ops — ``ops/pallas_utils.py``), which is what the CPU tier-1
parity suite pins; on a TPU they compile through Mosaic or raise.

Hardware notes (TPU v5e; ``tests/test_aot_tpu.py`` compiles these for a
described chip, ``chip_smoke.py`` runs them against their references):
block fetches are ``(block_size, KV, D)`` tiles pipelined by the grid (each
of a row's blocks is fetched exactly once — the grid is per row with all
heads in one cell). Decode and verify lower and match at GPT-2-small widths
(block 16, D 64, bf16); the score dots accumulate in f32
(``preferred_element_type``) and round to the operand dtype — what the
dense path's einsum does on a TPU — and ``sqrt(depth)`` is taken in f32
(v5e has no bf16 ``sqrt``). The sampling kernel lowers for temperature-only
sampling; Mosaic lowers neither ``top_k`` nor ``sort``, so top-k / top-p
under ``engine.decode_kernel: pallas`` is a compile error on the chip —
keep ``decode_kernel: xla`` for filtered sampling. The CPU-scale tests run
tiny blocks under the interpreter, where tiling constraints do not apply.

Registered in ``analysis/kernels.py::KERNEL_PARITY`` as ``paged-decode``
(and ``fused-sample``/``paged-verify``): graftlint's kernel-discipline
pass (GL1001–GL1004) holds every ``pallas_call`` here behind the
``pallas_utils`` gate and fails the tree if these entries lose their XLA
reference or parity test (docs/STATIC_ANALYSIS.md).
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from trlx_tpu.ops.pallas_utils import (
    LANES,
    align_rows,
    clamp_block_table,
    dot_precision,
    pad_bias_to,
    paged_pool_grid_spec,
    resolve_interpret,
)
from trlx_tpu.ops.sampling import process_logits

__all__ = [
    "paged_attention_decode",
    "paged_attention_decode_reference",
    "paged_verify_attention",
    "fused_sample",
    "sample_token_fused",
]


# ---------------------------------------------------------------------------
# paged-attention decode
# ---------------------------------------------------------------------------


def _paged_decode_kernel(
    tbl_ref,  # scalar-prefetch (B, TB) int32 — drives the k/v index maps
    q_ref,  # (1, H, D)
    bias_ref,  # (1, HB, Sp) f32 additive bias rows (slot-causal + validity
    #   [+alibi]); HB is 1 (head-uniform mask) or H (per-head ALiBi slopes)
    k_ref,  # (1, bs, KV, D) — pool block tbl[b, j], in place
    v_ref,  # (1, bs, KV, D)
    o_ref,  # (1, H, D)
    k_buf_ref,  # VMEM scratch (Sa, KV, D): the row's K, assembled per block
    v_buf_ref,  # VMEM scratch (Sa, KV, D): the row's V, assembled per block
    *,
    seq_len: int,  # S — logical cache columns (the dense view's width)
    block_size: int,
    num_blocks: int,  # TB
    group: int,  # query heads per kv head (GQA)
    head_dim: int,
):
    j = pl.program_id(1)
    # assembly steps: land this block's K/V in the row's VMEM buffers. All
    # compute waits for the full row — computing scores per block would
    # split the einsum's free dim, which is NOT bit-preserving for tiny
    # blocks (block_size=1 lowers the degenerate dot differently), and
    # bit-parity with the gather path's dense ops is the contract here.
    k_buf_ref[pl.ds(j * block_size, block_size), :, :] = k_ref[0]
    v_buf_ref[pl.ds(j * block_size, block_size), :, :] = v_ref[0]

    @pl.when(j == num_blocks - 1)
    def _finish():
        # the dense path on the per-row slice, op for op: GQA repeat;
        # scores = einsum(q, k) / sqrt(depth); scores += bias;
        # probs = softmax(f32(scores)).astype(dtype); out = einsum(probs, v).
        # Batch-dim (row) slicing is bit-preserving on the dense lowering —
        # making h a dot *batch* dim is not, which is why the grid is per
        # row with all heads in one cell.
        # Mosaic wants a 32-bit matmul accumulator and v5e has no bf16
        # sqrt: accumulate in f32 and round back to the operand dtype (a
        # no-op for f32, and what the dense bf16 einsum does on a TPU),
        # and take sqrt(depth) in f32, rounded to the same value the dense
        # path's sqrt in the operand dtype gives.
        q = q_ref[...]  # (1, H, D)
        k = k_buf_ref[0:seq_len, :, :]
        vv = v_buf_ref[0:seq_len, :, :]
        if group > 1:
            k = jnp.repeat(k, group, axis=1)
            vv = jnp.repeat(vv, group, axis=1)
        precision = dot_precision(q.dtype)
        raw = jnp.einsum(
            "thd,shd->hts", q, k,
            precision=precision, preferred_element_type=jnp.float32,
        ).astype(q.dtype)  # (H, 1, S)
        scores = raw / jnp.sqrt(jnp.float32(head_dim)).astype(raw.dtype)
        # (HB, 1, S) broadcasts over heads exactly like the dense path's
        # [B, HB, T, S] bias against its [B, H, T, S] scores
        bias = jnp.swapaxes(bias_ref[0:1, :, 0:seq_len], 0, 1)
        scores = scores + bias.astype(scores.dtype)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(
            raw.dtype
        )
        out = jnp.einsum(
            "hts,shd->thd", probs, vv,
            precision=precision, preferred_element_type=jnp.float32,
        )  # (1, H, D)
        o_ref[...] = out.astype(o_ref.dtype)


def paged_attention_decode(
    q: jax.Array,  # (B, H, D) single-step queries (rotary already applied)
    k_pool: jax.Array,  # (NB, bs, KV, D) — the persistent block pool
    v_pool: jax.Array,  # (NB, bs, KV, D)
    block_table: jax.Array,  # (B, TB) int32; out-of-range ids clamp (their
    #   lanes are bias-masked or belong to frozen rows whose output is dropped)
    bias: jax.Array,  # (B, HB, S) additive f32 bias (0 visible / -1e9
    #   masked [+ ALiBi]); HB is 1, or H for per-head slopes
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One decode step of attention, reading K/V through the block table.

    Returns ``(B, H, D)`` in ``q.dtype`` — bit-identical to gathering the
    pool into a dense ``[B, S, KV, D]`` view and running the dense einsum
    attention with the same ``bias`` (pinned by the parity suite). The pool
    is only read; the current step's K/V must already be committed.
    """
    B, H, D = q.shape
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
    S = bias.shape[2]
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
        _paged_decode_kernel,
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
        q_block=(1, H, D),
        bias_block=(1, HB, S_pad),
        out_block=(1, H, D),
        scratch_rows=S_align,
        k_dtype=k_pool.dtype,
        v_dtype=v_pool.dtype,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
    )(tbl, q, bias_p, k_pool, v_pool)


def paged_attention_decode_reference(
    q: jax.Array,  # (B, H, D)
    k_pool: jax.Array,  # (NB, bs, KV, D)
    v_pool: jax.Array,  # (NB, bs, KV, D)
    block_table: jax.Array,  # (B, TB)
    bias: jax.Array,  # (B, HB, S); HB is 1 or H (per-head ALiBi)
) -> jax.Array:
    """Gather-then-dense oracle: the exact computation the gather path's
    dense einsum attention performs on the gathered view (the parity
    reference)."""
    B, H, D = q.shape
    NB, bs, KV, _ = k_pool.shape
    S = bias.shape[2]

    def view(pool):
        v = pool[jnp.minimum(block_table, NB - 1)]  # (B, TB, bs, KV, D)
        v = v.reshape(B, -1, KV, D)[:, :S]
        if KV < H:
            v = jnp.repeat(v, H // KV, axis=2)
        return v

    k, v = view(k_pool), view(v_pool)
    depth = jnp.asarray(D, q.dtype)
    scores = jnp.einsum("bthd,bshd->bhts", q[:, None], k) / jnp.sqrt(depth)
    scores = scores + bias[:, :, None, :].astype(scores.dtype)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhts,bshd->bthd", probs, v)
    return out[:, 0]


# ---------------------------------------------------------------------------
# multi-position speculative verify
# ---------------------------------------------------------------------------


def paged_verify_attention(
    q: jax.Array,  # (B, G+1, H, D) probe-column queries (rotary applied)
    k_pool: jax.Array,  # (NB, bs, KV, D) — the persistent block pool
    v_pool: jax.Array,  # (NB, bs, KV, D)
    block_table: jax.Array,  # (B, TB) int32; out-of-range ids clamp
    bias: jax.Array,  # (B, HB, G+1, S) additive f32 slot-causality bias
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Speculative verify: the target model scoring ``k+1`` probe columns
    (the re-fed last accepted token plus ``k`` draft tokens) against the
    row's full committed prefix, in place through the block table.

    This IS a prefill-shaped pass — ``T = G+1`` queries over the same
    ``S``-wide key row, with the per-row probe window's slot-causality
    (each probe sees the committed prefix plus the *earlier* probe columns
    of its own row, nothing later and nothing of other rows' windows)
    carried entirely by the additive ``bias`` the transformer builds for
    vector cache indices, and the probe K/V committed by the caller with
    the same drop-mode writes as any chunk. So the entry deliberately
    shares the prefill kernel's body rather than cloning it: delegation is
    the bit-parity guarantee (one grid, one op sequence, one set of
    lowering rules — the three documented landmines stay fixed in exactly
    one place). The name exists as the verify path's seam: shape checks
    for the probe window here, and a single point to split the lowering
    later if the verify's skinny ``T`` ever wants its own schedule.
    """
    from trlx_tpu.ops.paged_prefill import paged_prefill_attention

    B, T, H, D = q.shape
    if bias.shape[2] != T:
        raise ValueError(
            f"bias probe dim {bias.shape[2]} != verify window T={T}"
        )
    return paged_prefill_attention(
        q, k_pool, v_pool, block_table, bias, interpret=interpret
    )


# ---------------------------------------------------------------------------
# fused temperature / top-k / top-p sampling
# ---------------------------------------------------------------------------


def _fused_sample_kernel(
    logits_ref,  # (1, V) f32 — post-adjust, post-eos-block raw logits
    *rest,
    temperature: float,
    top_k: int,
    top_p: float,
    do_sample: bool,
):
    if do_sample:
        gumbel_ref, tok_ref, lp_ref = rest
    else:
        tok_ref, lp_ref = rest
    row = logits_ref[...]  # (1, V)
    # the filter is literally ops/sampling.py::process_logits traced into
    # the kernel: identical sort / softmax / cumsum / threshold ops, but the
    # intermediates live in VMEM instead of round-tripping XLA HBM buffers
    filtered = process_logits(row, temperature, top_k, top_p)
    if do_sample:
        # categorical == argmax(gumbel + logits); the noise is an input so
        # the draw is jax.random.categorical's draw to the bit
        choice = jnp.argmax(gumbel_ref[...] + filtered, axis=-1)  # (1,)
    else:
        choice = jnp.argmax(filtered, axis=-1)
    # the chosen logprob by one-hot select + sum (exact: one term, rest 0.0);
    # Mosaic lowers no (1, V)[(1, 1)] gather
    cols = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    lp = jnp.sum(
        jnp.where(cols == choice[:, None], jax.nn.log_softmax(row, axis=-1), 0.0),
        axis=-1,
    )
    tok_ref[...] = jnp.broadcast_to(
        choice[:, None].astype(jnp.int32), tok_ref.shape
    )
    lp_ref[...] = jnp.broadcast_to(lp[:, None], lp_ref.shape)


def fused_sample(
    logits: jax.Array,  # (B, V) f32 raw logits (already adjusted/eos-blocked)
    gumbel: Optional[jax.Array],  # (B, V) f32 noise, or None for greedy
    *,
    temperature: float,
    top_k: int,
    top_p: float,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One fused kernel per row: filter + draw + behavior logprob.

    Returns ``(token [B] int32, logprob [B] f32)`` bit-identical to
    ``process_logits`` → ``jax.random.categorical`` (with ``gumbel`` being
    that categorical's own noise) → ``log_softmax`` gather.
    """
    interpret = resolve_interpret(interpret)
    B, V = logits.shape
    do_sample = gumbel is not None
    kernel = functools.partial(
        _fused_sample_kernel,
        temperature=temperature,
        top_k=top_k,
        top_p=top_p,
        do_sample=do_sample,
    )
    # Mosaic wants the lane (vocab) dim a multiple of 128: on chip the row
    # is padded with -inf logits (never drawn, exp() == 0 in every softmax)
    # and zero noise; under the interpreter V stays exact. Rows ride a unit
    # middle dim so each (1, Vp) block equals the operand's trailing dims.
    Vp = align_rows(V, interpret)
    pads = ((0, 0), (0, Vp - V))
    args = [jnp.pad(logits, pads, constant_values=-jnp.inf)]
    if do_sample:
        args.append(jnp.pad(gumbel, pads))
    row_spec = pl.BlockSpec((None, 1, Vp), lambda b: (b, 0, 0))
    out_spec = pl.BlockSpec((None, 1, LANES), lambda b: (b, 0, 0))
    tok, lp = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[row_spec] * len(args),
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, LANES), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(*(a[:, None, :] for a in args))
    return tok[:, 0, 0], lp[:, 0, 0]


def sample_token_fused(
    logits: jax.Array,  # [B, V] raw last-position logits
    step_out,
    sample_rng: jax.Array,  # [B, 2] per-row keys (per-row RNG is required)
    config,  # GenerationConfig
    step: jax.Array,  # [B] per-slot decode steps
    adjust_logits=None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Drop-in fused twin of ``ops/sampling.py::sample_token_from_logits``
    for the kernel decode path — same adjust-hook / eos-blocking prologue
    (cheap elementwise XLA), then one :func:`fused_sample` call instead of
    the XLA sort pipeline. Bit-identical outputs (parity suite)."""
    if adjust_logits is not None:
        logits = adjust_logits(step_out, logits)
    logits = logits.astype(jnp.float32)
    if config.eos_token_id is not None and config.min_new_tokens > 0:
        block_eos = jnp.asarray(step < config.min_new_tokens)
        if block_eos.ndim:
            block_eos = block_eos[:, None]
        logits = jnp.where(
            block_eos
            & (jnp.arange(logits.shape[-1])[None, :] == config.eos_token_id),
            -jnp.inf,
            logits,
        )
    gumbel = None
    if config.do_sample:
        if sample_rng.ndim != 2:
            raise ValueError(
                "sample_token_fused requires per-row key chains "
                "([B, 2] keys) — the kernel decode path implies per_row_rng"
            )
        V = logits.shape[-1]
        # exactly the noise jax.random.categorical(key, row) draws per row
        gumbel = jax.vmap(
            lambda k: jax.random.gumbel(k, (V,), jnp.float32)
        )(sample_rng)
    return fused_sample(
        logits,
        gumbel,
        temperature=config.temperature,
        top_k=config.top_k,
        top_p=config.top_p,
        interpret=interpret,
    )
