"""Grouped (per-expert) matmul of the dropless MoE path.

``grouped_matmul(lhs [m, k], rhs [G, k, n], group_sizes [G])`` multiplies
each run of rows by its group's matrix: rows ``[Σ sizes[:g], Σ sizes[:g+1])``
by ``rhs[g]``. Rows past the last group hold whatever the kernel left (the
caller masks them). Two kernels compute it, chosen from what can be observed
at trace time and nothing else:

- **short groups on one TPU device** (backend ``tpu``; no global mesh, or a
  mesh of one device; a traced operand; fewer than ``SHORT_GROUP`` rows a
  group on average): the megablox Pallas kernels that ship with jax
  (``jax.experimental.pallas.ops.tpu.megablox.gmm``, differentiable: its
  backward is ``gmm`` with ``rhs`` transposed for the rows and ``tgmm`` for
  the kernels) with a 128-row tile. ``jax.lax.ragged_dot`` on TPU becomes a
  Mosaic kernel too, but with tiles 512 x 512 x 512 whatever the row count:
  a decode step's 512 rows in 64 groups of about 8 pay a 512-row tile a
  group, 0.84 ms a matmul where this kernel takes 0.37 (PERF.md, PR 28,
  which also has the row counts at which the cut was measured).
- **anywhere else** (long groups, CPU, GPU, a mesh of several devices, an
  eager call): ``jax.lax.ragged_dot``. Long groups fill the compiler's tile:
  ``gmm`` with a 512-row tile is still a fifth to a quarter faster there, 4%
  of the benchmark's MoE cycle, but every distinct Pallas call costs about
  0.3 s of tracing and lowering at every start, compile cache or not, and
  prefill, scoring and the train step would add a dozen. Under a mesh GSPMD
  places ``ragged_dot`` itself (under ``fsdp=2, model=2`` it gathers the
  operands and every device computes the whole of it:
  ``tests/test_aot_tpu.py``); a Pallas custom call it cannot place without a
  ``shard_map``, which no cell has yet measured. An eager call (flax's
  ``module.init`` on its dummy batch) would compile the kernel and a dozen
  small programs for shapes nothing else runs.

Backend and mesh are the process's: ``jax.default_backend()`` and the mesh
the trainer hands ``parallel.mesh.set_global_mesh``. A ``jit`` placed on CPU
devices inside a TPU process, or operands sharded over a mesh that was never
set, are not seen, and would fail to lower or to partition.

Operands keep their dtype (bf16 in, float32 accumulation, bf16 out), the same
as ``ragged_dot``. The library's kernels take the ambient matmul precision
where they are traced; Mosaic refuses a raised one for bf16 ("Bad lhs type").
"""

from typing import Tuple

import jax
import jax.numpy as jnp

from trlx_tpu.ops.pallas_utils import pad_to, resolve_interpret

__all__ = ["grouped_matmul", "gmm_tiles", "gmm_rows_visited", "ROW_TILE", "SHORT_GROUP"]

# one MXU pass on a v5e; of 16 to 512 the fastest at a decode step's shapes
ROW_TILE = 128
# mean rows a group under which the 128-row kernel beat ragged_dot in every
# matmul timed on the chip, forward and backward, at 64 groups and at 8; at
# 512 its gate and up forward no longer does (PERF.md, PR 28, finding 3)
SHORT_GROUP = 256


def _short_groups_on_one_tpu(lhs: jax.Array, num_groups: int) -> bool:
    from trlx_tpu.parallel.mesh import get_global_mesh

    mesh = get_global_mesh()
    return (
        jax.default_backend() == "tpu"
        and (mesh is None or mesh.size == 1)
        and isinstance(lhs, jax.core.Tracer)
        and lhs.shape[0] < SHORT_GROUP * num_groups
    )


def _feature_tile(dim: int, cap: int) -> int:
    """``cap`` where it divides ``dim``, else the largest divisor of ``dim``
    below it that is a multiple of 128, else (an unaligned width) all of
    ``dim``, which is a legal block whatever its size."""
    for tile in range(cap, 0, -128):
        if dim % tile == 0:
            return tile
    return dim


def gmm_tiles(k: int, n: int, itemsize: int = 2) -> Tuple[int, int, int]:
    """megablox tiles ``(tm, tk, tn)`` for ``[m, k] x [G, k, n]``:
    ``ROW_TILE`` rows; ``tk`` and ``tn`` 1024 for two-byte operands and 512
    for four-byte ones (the double-buffered blocks and the float32
    accumulator of the largest of the three kernels have to fit Mosaic's
    default 16 MiB of scoped VMEM), or the nearest divisor below."""
    cap = 1024 if itemsize <= 2 else 512
    return ROW_TILE, _feature_tile(k, cap), _feature_tile(n, cap)


def gmm_rows_visited(group_sizes: jax.Array, tm: int = ROW_TILE) -> jax.Array:
    """Rows the kernel computes: ``tm`` for every (group, row tile) pair in
    which the group has a row. ``Σ group_sizes`` over it is how full the row
    tiles are."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    tiles = jnp.where(group_sizes > 0, (ends + tm - 1) // tm - starts // tm, 0)
    return jnp.sum(tiles) * tm


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """``[m, k] x [G, k, n] -> [m, n]`` by groups of rows."""
    if _short_groups_on_one_tpu(lhs, rhs.shape[0]):
        tiles = gmm_tiles(lhs.shape[1], rhs.shape[2], lhs.dtype.itemsize)
        return _gmm(lhs, rhs, group_sizes, tiles, resolve_interpret(None))
    # bf16 operands have one precision; saying so keeps the kernel compiling
    # under a global jax_default_matmul_precision=highest, which Mosaic
    # refuses for bf16 ("Bad lhs type")
    precision = jax.lax.Precision.DEFAULT if lhs.dtype == jnp.bfloat16 else None
    return jax.lax.ragged_dot(lhs, rhs, group_sizes, precision=precision)


def _gmm(lhs, rhs, group_sizes, tiles: Tuple[int, int, int], interpret: bool) -> jax.Array:
    """megablox ``gmm`` on rows padded to the row tile. Neither it nor its
    backward writes the rows outside every group, so those rows go in through
    a select, whose own backward zeroes their gradient: what the kernels left
    there would otherwise reach the padding tokens' inputs."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m = lhs.shape[0]
    in_a_group = jnp.arange(m) < jnp.sum(group_sizes)
    lhs = pad_to(jnp.where(in_a_group[:, None], lhs, 0), tiles[0], 0)
    out = gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype, tiles, interpret=interpret)
    return out[:m]
