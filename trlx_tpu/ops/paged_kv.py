"""Paged KV cache: fixed-size KV blocks + per-slot block tables (L0).

The dense per-slot caches (``ops/sampling.py::generate``,
``ops/slot_refill.py``) allocate ``[B, S = P + N]`` KV rows up front — an
HBM ceiling of ``slots × max_length`` that is mostly dead space whenever
responses end early or prompts share prefixes. Here the persistent KV state
is a **block pool**: ``max_blocks`` fixed-size blocks of ``block_size``
slots each, plus a per-slot **block table** mapping logical cache columns
``s`` to pool rows ``table[b, s // block_size]``. Blocks are allocated as
sequences actually grow (host allocator, ``trlx_tpu/engine/allocator.py``)
and freed at harvest, so the pool's high-water tracks *live tokens*; shared
prompt prefixes point several tables at one refcounted block
(``trlx_tpu/engine/prefix_cache.py``) — the vLLM PagedAttention layout
(Kwon et al. 2023), rebuilt functionally for jitted JAX programs.

Bit-parity strategy (pinned by ``tests/test_engine.py``): attention never
learns about blocks. Each compiled program **gathers** the pool through the
table into the exact dense ``[rows, S, kvH, D]`` view the model already
consumes, runs the *unchanged* dense compute (prefill / slot-refill decode
segment), and **scatters** the newly written span back into the pool. The
gathered view is bit-identical to the dense backend's cache in every
attention-visible position (committed blocks reproduce committed values;
unallocated table entries point at the reserved all-zeros block 0; recycled
blocks may hold stale values only at slot-masked positions, where the
``-1e9`` bias underflows softmax to exactly ``0.0`` — a zero contribution,
same as the dense cache's zeros). Hence paged decode is bit-identical to
dense slot-refill decode, which is bit-identical to plain ``generate``
under per-row RNG.

The dense view is a per-program *temporary* (alive only inside one XLA
program); the pool + table are the persistent state. The Pallas
paged-attention decode kernel that reads blocks in place — removing the
transient view from the decode inner loop — lives in
``ops/paged_attention.py`` (selected by ``engine.decode_kernel: pallas``);
the gather path here stays as the bit-equivalence reference it must
reproduce, and remains the only prefill path.

Pool layout reuses the model cache structure verbatim:
``init_cache_fn(max_blocks, block_size)`` — the block axis rides the cache's
batch axis, ``block_size`` its length axis. Unscanned leaves are
``[NB, bs, kvH, D]`` (per-layer list of ``{"k","v"}``), scanned leaves
``[L, NB, bs, kvH, D]``; the layout test is ``leaf.ndim - 4`` exactly as in
``ops/slot_refill.py``.
"""

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "ZERO_BLOCK",
    "PagedKV",
    "PagedSpec",
    "num_table_blocks",
    "init_paged_kv",
    "gather_view",
    "scatter_span",
    "scatter_steps",
    "attach_block_table",
    "detach_block_table",
    "kv_bytes",
    "block_bytes",
]

# Physical block 0 is reserved as the permanent all-zeros block: fresh table
# entries point here, so gathering an unallocated region reproduces the
# dense cache's zeros. The allocator never hands it out and no scatter ever
# targets it (valid writes always go through allocated table entries;
# padding/invalid lanes use an out-of-range id and scatter-drop).
ZERO_BLOCK = 0


class PagedSpec(NamedTuple):
    """Static paged-cache geometry (compile-time constants)."""

    block_size: int
    max_blocks: int  # pool rows, including the reserved zero block


class PagedKV(NamedTuple):
    """The persistent paged KV state threaded through engine programs.

    ``pool`` is a model-cache pytree over ``(max_blocks, block_size)``;
    ``block_table`` is ``[B, TB]`` int32 of physical block ids (host-managed
    between segments; pure data inside compiled programs)."""

    pool: Any
    block_table: jax.Array


def num_table_blocks(slots: int, block_size: int) -> int:
    """Table width: blocks needed to cover ``slots`` logical columns."""
    return -(-slots // block_size)


def init_paged_kv(
    init_cache_fn, spec: PagedSpec, batch_size: int, slots: int
) -> PagedKV:
    """All-zeros pool + all-zero-block tables for ``batch_size`` slots.
    ``init_cache_fn`` gives ``[.., KV, D]`` leaves, never lane-packed ones
    (``make_kv_cache(..., lane_packed=False)``: the paged kernels read a
    block's own layout, and ``Attention`` refuses a packed pool)."""
    return PagedKV(
        pool=init_cache_fn(spec.max_blocks, spec.block_size),
        block_table=jnp.zeros(
            (batch_size, num_table_blocks(slots, spec.block_size)), jnp.int32
        ),
    )


def _scanned(leaf: jax.Array) -> bool:
    # pool/cache leaves: [NB, bs, kvH, D] per layer, or [L, NB, bs, kvH, D]
    # when a scanned stack put the layer axis in front
    return leaf.ndim - 4 == 1


def gather_view(pool: Any, block_table: jax.Array, slots: int) -> Any:
    """Dense ``[rows, slots, kvH, D]`` cache view of ``block_table``'s rows —
    the exact pytree the model's decode/prefill forwards consume. Table ids
    are clamp-gathered (jnp default), so out-of-range padding ids read the
    last pool row; such lanes are never attention-visible (their slot mask
    is 0) and never scattered back (drop-mode writes)."""
    R, TB = block_table.shape

    def leaf_view(leaf):
        if leaf is None:
            return None
        bs = leaf.shape[-3]
        if _scanned(leaf):
            v = leaf[:, block_table]  # [L, R, TB, bs, kvH, D]
            v = v.reshape(v.shape[:1] + (R, TB * bs) + v.shape[4:])
            return v[:, :, :slots]
        v = leaf[block_table]  # [R, TB, bs, kvH, D]
        v = v.reshape((R, TB * bs) + v.shape[3:])
        return v[:, :slots]

    return jax.tree_util.tree_map(leaf_view, pool, is_leaf=lambda x: x is None)


def scatter_span(
    pool: Any,
    block_table: jax.Array,  # [R, TB] — rows being written
    dense_rows: Any,  # dense cache view [R, >= start+length, kvH, D]
    start: int,
    length: int,
) -> Any:
    """Commit slots ``[start, start + length)`` of a dense row view into the
    pool (the prefill write-back). Static span; drop-mode scatter, so
    padding rows (tables full of an out-of-range id) write nothing."""
    if length <= 0:
        return pool
    R, TB = block_table.shape
    cols = start + jnp.arange(length)  # [length]

    def leaf_scatter(pool_leaf, view_leaf):
        if pool_leaf is None:
            return None
        blk_size = pool_leaf.shape[-3]
        blk = block_table[:, cols // blk_size]  # [R, length]
        off = jnp.broadcast_to((cols % blk_size)[None, :], (R, length))
        if _scanned(pool_leaf):
            vals = view_leaf[:, :, start : start + length]
            return pool_leaf.at[:, blk, off].set(
                vals.astype(pool_leaf.dtype), mode="drop"
            )
        vals = view_leaf[:, start : start + length]
        return pool_leaf.at[blk, off].set(vals.astype(pool_leaf.dtype), mode="drop")

    return jax.tree_util.tree_map(
        leaf_scatter, pool, dense_rows, is_leaf=lambda x: x is None
    )


def scatter_steps(
    pool: Any,
    block_table: jax.Array,  # [B, TB]
    dense_view: Any,  # post-segment dense cache view [B, S, kvH, D]
    base_cols: jax.Array,  # [B] first written column per row (P + step before)
    counts: jax.Array,  # [B] columns actually written (step advance)
    max_steps: int,  # static bound: the segment length
) -> Any:
    """Commit each row's decode-segment writes — columns
    ``[base_cols[b], base_cols[b] + counts[b])`` — back into the pool.
    Rows that froze mid-segment commit only their live writes; the dense
    backend's harmless dead writes (done rows re-writing masked columns)
    are simply not carried over, which is equivalent under the slot mask."""
    B, TB = block_table.shape
    j = jnp.arange(max_steps)[None, :]  # [1, max_steps]
    cols = base_cols[:, None] + j  # [B, max_steps]
    valid = j < counts[:, None]

    def leaf_scatter(pool_leaf, view_leaf):
        if pool_leaf is None:
            return None
        blk_size = pool_leaf.shape[-3]
        S = view_leaf.shape[-3]
        cols_safe = jnp.minimum(cols, S - 1)
        blk = jnp.take_along_axis(block_table, cols_safe // blk_size, axis=1)
        blk = jnp.where(valid, blk, pool_leaf.shape[-4])  # invalid → drop
        off = cols_safe % blk_size
        if _scanned(pool_leaf):
            vals = jax.vmap(lambda row, c: row[:, c], in_axes=(1, 0), out_axes=1)(
                view_leaf, cols_safe
            )  # [L, B, max_steps, kvH, D]
            return pool_leaf.at[:, blk, off].set(
                vals.astype(pool_leaf.dtype), mode="drop"
            )
        vals = jax.vmap(lambda row, c: row[c])(view_leaf, cols_safe)
        return pool_leaf.at[blk, off].set(vals.astype(pool_leaf.dtype), mode="drop")

    return jax.tree_util.tree_map(
        leaf_scatter, pool, dense_view, is_leaf=lambda x: x is None
    )


def attach_block_table(pool: Any, block_table: jax.Array) -> Any:
    """Per-layer model-cache views of the pool that CARRY the block table —
    the cache pytree the kernel decode path feeds ``apply_fn``. The model's
    attention (``models/transformer.py::Attention``) recognises the
    ``"block_table"`` leaf and reads/writes K/V through the table in place
    (``ops/paged_attention.py``) instead of expecting a dense view.

    Rows whose table entries are out of range (``>= max_blocks`` — frozen
    slots the decode loop poisons, bucket-padding refill rows) write
    nothing (drop-mode) and read clamped garbage their callers discard.
    """
    if isinstance(pool, list):  # per-layer [{"k", "v"}, ...]
        return [
            None if layer is None else {**layer, "block_table": block_table}
            for layer in pool
        ]
    # scanned layout {"k": [L, NB, bs, KV, D], ...}: nn.scan slices every
    # cache leaf along the layer axis, so the (tiny, int32) table is tiled
    L = pool["k"].shape[0]
    return {
        **pool,
        "block_table": jnp.broadcast_to(
            block_table[None], (L,) + block_table.shape
        ),
    }


def detach_block_table(cache: Any) -> Any:
    """Inverse of :func:`attach_block_table`: strip the table leaves, give
    back the bare pool pytree (what ``PagedKV.pool`` persists)."""
    if isinstance(cache, list):
        return [
            None
            if layer is None
            else {k: v for k, v in layer.items() if k != "block_table"}
            for layer in cache
        ]
    return {k: v for k, v in cache.items() if k != "block_table"}


def kv_bytes(cache: Any) -> int:
    """Total bytes of a KV pytree (dense cache, pool, or PagedKV pool) —
    the persistent-allocation number behind ``memory/kv_cache_bytes``."""
    if isinstance(cache, PagedKV):
        cache = cache.pool
    return int(
        sum(
            int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            for leaf in jax.tree_util.tree_leaves(cache)
        )
    )


def block_bytes(cache: Any) -> int:
    """Bytes of ONE block across all layers/k/v — multiply by
    blocks-in-use for the live-token-scaled high-water number."""
    if isinstance(cache, PagedKV):
        cache = cache.pool
    total = 0
    for leaf in jax.tree_util.tree_leaves(cache):
        nb = leaf.shape[-4]
        total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize // nb
    return int(total)
