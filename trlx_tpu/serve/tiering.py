"""Host-RAM tiering of evicted prefix blocks (docs/SERVING.md).

When the block pool (or a tenant quota) forces the prefix cache to evict a
committed entry, the KV bytes it took a prefill to produce are normally
gone — a re-arriving prompt pays the full re-prefill. :class:`HostTier` is
the second tier: the cache's ``spill`` hook copies the evicted block's
pool rows to a bounded host pool (keyed by the entry's content-chained
digest, which survives evict/re-insert cycles), and the engine's admission
path (``ContinuousEngine._prepare_row``) probes it for the chunks beyond
the device hit — a host hit allocates a fresh device block and writes the
saved bytes back instead of re-prefilling them.

Bit-equality by construction: a spill is ``device_get`` of committed
(immutable) block rows, a re-land is a verbatim ``.at[blocks].set`` of the
same bytes — no compute touches the values, so a re-landed prefix is
byte-identical to the device-resident prefix it was spilled from, which
the prefix-cache tests pin byte-identical to a cold prefill. Pinned across
block sizes in ``tests/test_serve.py``.

Sharp edges (docs/SERVING.md):

- The tier is flushed whenever the engine adopts changed params
  (``swap_params`` / ``begin_collection``) — spilled KV is only valid
  under the params that computed it, exactly like device-side entries.
- Spill/re-land move ``block_bytes`` per block over PCIe/host memory; the
  win is elastic: it pays off when re-prefill compute > transfer, which is
  the regime long shared prompts live in (no benchmark cell prices it
  yet).
- The write-back runs un-donated (CPU backends do not implement buffer
  donation and would warn); on a real accelerator a donated variant would
  avoid the transient pool copy.

Thread affinity: owned and touched ONLY by the thread driving the engine
(the serve pump, or the trainer's main thread) — same single-threaded
contract as the allocator and prefix cache. Serve-side metric snapshots go
through ``ServeMetrics``, never through direct cross-thread reads here.
"""

from collections import OrderedDict
from typing import Any, Dict

import numpy as np

__all__ = ["HostTier"]


def _read_block(pool: Any, block: int) -> Any:
    """Host (numpy) copy of one block's rows across every pool leaf."""
    import jax

    def rd(leaf):
        if leaf is None:
            return None
        if leaf.ndim - 4 == 1:  # scanned: [L, NB, bs, kvH, D]
            return np.asarray(leaf[:, block])
        return np.asarray(leaf[block])

    return jax.tree_util.tree_map(rd, pool, is_leaf=lambda x: x is None)


def _write_blocks(pool: Any, blocks: Any, vals: Any) -> Any:
    """New pool with each ``vals[i]`` written verbatim into ``blocks[i]``'s
    rows — ONE copy-on-write of each pool leaf for the whole run (the
    per-block variant cost a full pool copy per block, which dominated the
    re-land path for multi-block prefixes)."""
    import jax
    import jax.numpy as jnp

    idx = np.asarray(blocks, np.int32)

    def wr(leaf, *vs):
        if leaf is None:
            return None
        # stack host-side: one device put for the whole run, not one per
        # block (the per-val jnp.asarray puts dominated the re-land cost)
        if leaf.ndim - 4 == 1:  # scanned: [L, NB, bs, kvH, D]
            stacked = np.stack([np.asarray(v) for v in vs], 1)
            return leaf.at[:, idx].set(jnp.asarray(stacked, leaf.dtype))
        stacked = np.stack([np.asarray(v) for v in vs], 0)
        return leaf.at[idx].set(jnp.asarray(stacked, leaf.dtype))

    return jax.tree_util.tree_map(wr, pool, *vals, is_leaf=lambda x: x is None)


class HostTier:
    """Bounded LRU host pool of spilled prefix-block KV, digest-keyed."""

    def __init__(self, max_blocks: int, block_bytes: int = 0):
        if max_blocks < 1:
            raise ValueError(f"host tier needs max_blocks >= 1, got {max_blocks}")
        self.max_blocks = int(max_blocks)
        self.block_bytes = int(block_bytes)  # informational (metrics)
        self._pool: "OrderedDict[bytes, Any]" = OrderedDict()
        # lifetime counters, read via snapshot() from the owning thread
        self.spilled = 0
        self.evicted = 0
        self.hits = 0
        self.misses = 0
        self.relanded_blocks = 0

    def __len__(self) -> int:
        return len(self._pool)

    def __contains__(self, digest: bytes) -> bool:
        return digest in self._pool

    # -- owning-thread operations ----------------------------------------

    def spill(self, digest: bytes, pool: Any, block: int) -> None:
        """Copy ``block``'s rows host-side under ``digest`` (LRU insert);
        beyond capacity the least-recently-touched spill is dropped."""
        if digest in self._pool:
            self._pool.move_to_end(digest)
            return
        self._pool[digest] = _read_block(pool, block)
        self.spilled += 1
        while len(self._pool) > self.max_blocks:
            self._pool.popitem(last=False)
            self.evicted += 1

    def probe(self, digest: bytes) -> bool:
        hit = digest in self._pool
        if hit:
            self.hits += 1
        else:
            self.misses += 1
        return hit

    def reland(self, digest: bytes, pool: Any, block: int) -> Any:
        """Write the spilled bytes back into a freshly allocated device
        ``block``; returns the new pool. The host copy is retained (the
        device entry may be evicted again before the host LRU turns)."""
        return self.reland_many([digest], pool, [block])

    def reland_many(self, digests: Any, pool: Any, blocks: Any) -> Any:
        """Re-land a consecutive run of spilled chunks in one pool update:
        each pool leaf is copy-on-written ONCE for the whole run instead of
        once per block."""
        vals = [self._pool[d] for d in digests]
        for d in digests:
            self._pool.move_to_end(d)
        self.relanded_blocks += len(vals)
        return _write_blocks(pool, blocks, vals)

    def clear(self) -> None:
        """Drop every spilled block — params changed, the bytes are void."""
        self._pool.clear()

    def snapshot(self) -> Dict[str, float]:
        """Counter snapshot for the serve metrics pump (owning thread)."""
        return {
            "blocks": float(len(self._pool)),
            "bytes": float(len(self._pool) * self.block_bytes),
            "spilled": float(self.spilled),
            "evicted": float(self.evicted),
            "hits": float(self.hits),
            "misses": float(self.misses),
            "relanded": float(self.relanded_blocks),
        }
