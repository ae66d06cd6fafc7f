"""Shared Pallas TPU plumbing for the repo's kernels.

Every Pallas kernel module (``ops/flash_attention.py``,
``ops/paged_attention.py``) needs the same decisions made the same way, so
they live here once:

- **Interpret-mode default**: off-TPU, kernels run under the Pallas
  interpreter — the same kernel body executed as traced jax ops, which is
  what makes the CPU tier-1 bit-parity tests meaningful (interpret-mode
  ops are ordinary XLA ops on the same values). On a TPU every kernel
  compiles through Mosaic or raises: nothing gives way to a reference.
- **SMEM spec**: scalar operands live in SMEM.

Masking convention shared by the kernels: masked scores are driven to
``NEG_INF`` (or carry the dense path's ``-1e9`` additive bias) so that
``exp(masked - max)`` underflows to exactly ``0.0`` — which is what makes
recycled-block stale values contribute nothing to paged attention and
padded key slots contribute nothing to flash attention.
"""

from typing import Optional

import jax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "pltpu",
    "NEG_INF",
    "LANES",
    "default_interpret",
    "resolve_interpret",
    "smem_spec",
    "dot_precision",
    "pad_to",
    "align_rows",
    "clamp_block_table",
    "pad_bias_to",
    "paged_pool_grid_spec",
]

NEG_INF = -1e30
# lane width for per-row stats (lse/delta/sampled token); 8 is the f32
# sublane minimum and the "equal to the overall array dim" rule makes the
# last dim legal
LANES = 8


def default_interpret() -> bool:
    """Kernels compile for real only on TPU; every other backend runs the
    Pallas interpreter (bit-parity tests pin the interpret path on CPU)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The per-call ``interpret=`` knob: ``None`` = backend default."""
    return default_interpret() if interpret is None else bool(interpret)


def smem_spec() -> pl.BlockSpec:
    """Whole-operand scalar spec in SMEM."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def dot_precision(dtype):
    """``precision=`` for an in-kernel dot on ``dtype`` operands: f32 follows
    ``jax_default_matmul_precision`` like the dense path it mirrors; narrower
    operands pin DEFAULT — one MXU pass is already exact on them, and Mosaic
    refuses an fp32 contraction on bf16 ("Bad lhs type") when a caller has
    raised the default."""
    import jax.numpy as jnp

    return None if dtype == jnp.float32 else jax.lax.Precision.DEFAULT


def pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    """Zero-pad ``axis`` up to the next multiple of ``mult``."""
    import jax.numpy as jnp

    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def align_rows(n: int, interpret: bool, lanes: int = 128) -> int:
    """Scratch/operand row count for a VMEM buffer: exact under the
    interpreter, rounded up to the hardware lane multiple on chip. Kernels
    read ``[0:n]`` slices either way, so alignment never changes bits."""
    return n if interpret else -(-n // lanes) * lanes


def clamp_block_table(block_table: jax.Array, num_blocks: int) -> jax.Array:
    """Block-table ids as safe int32 fetch indices: out-of-range entries
    (poisoned rows, frozen slots) clamp to the last pool block — their
    lanes are bias-masked or their outputs dropped, so the clamped fetch
    only has to be *legal*, never correct."""
    import jax.numpy as jnp

    return jnp.minimum(block_table.astype(jnp.int32), num_blocks - 1)


def pad_bias_to(bias: jax.Array, width: int) -> jax.Array:
    """Additive bias as the kernels consume it: f32, last (key) axis
    zero-padded to exactly ``width`` (the block-table span). Padded columns
    sit beyond ``seq_len`` and are never read by the compute slice."""
    import jax.numpy as jnp

    bias = bias.astype(jnp.float32)
    short = width - bias.shape[-1]
    if short <= 0:
        return bias
    widths = [(0, 0)] * (bias.ndim - 1) + [(0, short)]
    return jnp.pad(bias, widths)


def _row_block_spec(block) -> pl.BlockSpec:
    """Per-row spec under the ``(b, j, tbl)`` paged grid: block ``b`` along
    the leading (batch) axis, whole operand elsewhere."""
    zeros = (0,) * (len(block) - 1)
    return pl.BlockSpec(block, lambda b, j, tbl: (b,) + zeros)


def paged_pool_grid_spec(
    *,
    batch: int,
    table_blocks: int,
    block_size: int,
    kv_heads: int,
    head_dim: int,
    q_block,
    bias_block,
    out_block,
    scratch_rows: int,
    k_dtype,
    v_dtype,
):
    """The shared scalar-prefetch grid for pool-reading kernels.

    ``ops/paged_attention.py`` and ``ops/paged_prefill.py`` (and the verify
    entry built on the latter) all walk the same ``(B, TB)`` grid in which
    the scalar-prefetched block table *is* the K/V index map: grid cell
    ``(b, j)`` fetches pool block ``tbl[b, j]`` into VMEM, and per-row
    operands (q / bias / out) ride the batch axis. Factored here so the
    fourth kernel doesn't carry the fourth copy of this boilerplate
    (ISSUE 18) — the shape differences between decode (``q: (1, H, D)``)
    and prefill (``q: (1, T, H, D)``) are entirely in the block tuples.
    """
    pool_block = (1, block_size, kv_heads, head_dim)

    def pool_map(b, j, tbl):
        return (tbl[b, j], 0, 0, 0)

    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, table_blocks),
        in_specs=[
            _row_block_spec(q_block),
            _row_block_spec(bias_block),
            pl.BlockSpec(pool_block, pool_map),
            pl.BlockSpec(pool_block, pool_map),
        ],
        out_specs=_row_block_spec(out_block),
        scratch_shapes=[
            pltpu.VMEM((scratch_rows, kv_heads, head_dim), k_dtype),
            pltpu.VMEM((scratch_rows, kv_heads, head_dim), v_dtype),
        ],
    )
