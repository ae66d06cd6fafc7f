"""What a layer caches: one description, read by everything that is handed a cache pytree.

``models/transformer.py::make_kv_cache`` decides the shapes; this module owns
the three things every other reader needs to know of them: the VOCABULARY of
leaf names (each with its kind and the axis that is the row's slots), the
ACCOUNT of a cache pytree's bytes by kind (``cache_bytes``) and the REFUSAL of
the whole-row rollout paths (``refuse``: one table of what each path cannot
hold and why). A new cache layout is written in ``models/`` and gets a row of
``VOCABULARY``; lifting a path for a kind is deleting a row of ``REFUSED``.

**Lane-packed K and V.** A dense ``k`` / ``v`` leaf holds ``P = lane_heads(D,
KV)`` KV heads side by side in its minor axis, ``[B, slots, KV / P, P * D]``:
the row-major reshape of ``[B, slots, KV, D]``'s last two axes (``lane_pack``,
``lane_unpack``), so the bytes, the slot axis and every writer's index tuple
are what they were. At a head of 64 a ``[.., KV, 64]`` leaf would fill half of
each 128-lane row, and the TPU compiler then keeps the loop-carried cache
slot-minor, which makes a decode step's write of one slot a strided pass over
the leaf (PERF.md section 6, PR 61); two heads a row keep the channels minor
and the write in place. ``P`` is 1, and nothing is reshaped, at a head of 128
or more. A reader takes ``P`` from the leaf it is handed (``leaf.shape[-1] //
D``), never from the rule, so a cache made unpacked is read as it always was:
the paged pool (``ops/paged_kv.py::PagedKV``, ``[NB, bs, KV, D]``), the rows
bound for it and the dense view gathered from it are NOT packed
(``make_kv_cache(..., lane_packed=False)``), since ``ops/paged_attention.py``
and ``ops/paged_prefill.py`` read their own layout a block at a time.
"""

from collections import Counter
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import numpy as np

__all__ = ["KV", "POOLED", "RECURRENT", "LINEAR", "CONV", "LATENT", "INDEX", "KINDS", "VOCABULARY", "PATHS", "REFUSED",
           "describe", "cache_slots", "cache_bytes", "cacheless", "ring", "refuse", "lane_heads", "lane_pack", "lane_unpack", "kv_lane_heads"]

# the kinds of thing a layer keeps a sequence, in the words a refusal says them
KV, POOLED, RECURRENT, LINEAR, CONV, LATENT, INDEX = "kv", "pooled", "recurrent", "linear", "conv", "latent", "index"
KINDS = {
    KV: "per-head K and V",
    POOLED: "compressed keys beside K and V",
    RECURRENT: "recurrent state (beside K and V, or with its conv's rows a layer's whole cache)",
    LINEAR: "a recurrence's state as the layer's whole cache",
    CONV: "a short convolution's last input rows as the layer's whole cache",
    LATENT: "a latent in place of K and V",
    INDEX: "index keys beside a latent",
}


class Leaf(NamedTuple):
    kind: str
    slot_axis: Optional[int]  # the axis that is the row's slots, from the END (a stacked cache leads with its layers)
    beside: Tuple[Tuple[str, str], ...] = ()  # (a neighbour in the layer's dict, the kind this leaf is beside it)


# every leaf name `make_kv_cache` may give a layer's dict
VOCABULARY = {
    # [B, slots, KV heads / P, P * D], P = lane_heads(D, KV) heads side by side in a row (1 at a head of 128 or more: the
    # plain [B, slots, KV heads, D]); a window layer's is a ring where the window is shorter than the row
    "k": Leaf(KV, -3),
    "v": Leaf(KV, -3),
    "kbar": Leaf(POOLED, None),  # [B, KV heads, slots / stride, D]: the keys' mean-pool under a block selection
    # a state-space recurrence's state, float32: beside attention's K and V (falcon_h1), or with `conv` the whole cache of
    # a layer whose whole mixer it is (nemotron_h); the same kind and the same refusals either way
    "ssm": Leaf(RECURRENT, None),
    # a conv's last input rows: ALONE a gated short convolution layer's whole cache, [B, taps - 1, hidden] whatever the
    # row's length; beside `ssm` part of a state-space mixer's state, beside a delta rule's `state` part of its layer's
    "conv": Leaf(CONV, None, (("ssm", RECURRENT), ("state", LINEAR))),
    "state": Leaf(LINEAR, None),  # [B, heads, d, d] float32: a linear-attention or delta-rule layer's whole cache
    "ckv": Leaf(LATENT, -2),  # [B, slots, rank]: the normed latent keys and values are made from
    "k_rope": Leaf(LATENT, -2),  # [B, slots, rope]: the one roped key all heads share
    "latent": Leaf(LATENT, -2),  # [ckv | k_rope] in one row a slot, on a layer whose steps gather chosen slots
    "k_index": Leaf(INDEX, -2),  # [B, slots, index dim]: the one index key a slot of a layer that selects for itself
}


LANES = 128  # the minor axis of a TPU's tile: a row of a leaf narrower than this is padded to it, or the leaf is turned


def lane_heads(head_dim: int, kv_heads: int) -> int:
    """KV heads a dense ``k`` / ``v`` leaf holds side by side in one row of
    its minor axis: as many heads of ``head_dim`` as fill ``LANES``, where
    they fill it exactly and divide the layer's ``kv_heads``; 1 otherwise.
    THE rule: a function of the two shapes and of nothing else."""
    heads = LANES // head_dim if head_dim < LANES and LANES % head_dim == 0 else 1
    return heads if kv_heads % heads == 0 else 1


def lane_pack(x: jax.Array, heads: int) -> jax.Array:
    """``[..., KV, D]`` as the leaf holds it, ``[..., KV / heads, heads * D]``;
    ``x`` itself at 1."""
    return x if heads == 1 else x.reshape(x.shape[:-2] + (x.shape[-2] // heads, heads * x.shape[-1]))


def lane_unpack(x: jax.Array, heads: int) -> jax.Array:
    """``lane_pack``'s inverse."""
    return x if heads == 1 else x.reshape(x.shape[:-2] + (x.shape[-2] * heads, x.shape[-1] // heads))


def kv_lane_heads(cache: Any, head_dim: int) -> int:
    """KV heads a row of the ``k`` leaves of a cache pytree (arrays or
    shapes) holds, read off the leaves; 1 for a cache with no ``k``."""
    leaves = jax.tree_util.tree_flatten_with_path(cache)[0]
    return max((int(leaf.shape[-1]) // head_dim for path, leaf in leaves if path and getattr(path[-1], "key", None) == "k"), default=1)


class Held(NamedTuple):
    """One leaf of one layer's dict, described."""

    name: str
    kind: str
    slots: Optional[int]  # the slots its layer holds a row; None for a leaf without a slot axis
    bytes: int


def describe(cache: Any) -> List[Held]:
    """Every leaf of every layer's dict in a cache pytree (arrays or shapes: a
    list of layers, one stacked dict, a ``PagedKV``, a tuple of caches). The
    paged pool's ``block_table`` is no layer's leaf and is passed by, as is a
    layer that caches nothing (an empty dict has no leaf: ``cacheless``
    counts those); any other name ``VOCABULARY`` does not hold raises."""
    layers: Dict[str, Dict[str, Any]] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        name = getattr(path[-1], "key", None) if path else None
        if name is not None and name != "block_table":
            layers.setdefault(jax.tree_util.keystr(path[:-1]), {})[name] = leaf
    held = []
    for layer in layers.values():
        unknown = sorted(set(layer) - set(VOCABULARY))
        if unknown:
            raise ValueError(f"a layer's cache holds leaves {unknown} that ops/cache_layout.py::VOCABULARY does not know (it knows {sorted(VOCABULARY)})")
        for name, leaf in layer.items():
            entry = VOCABULARY[name]
            kind = next((kind for other, kind in entry.beside if other in layer), entry.kind)
            slots = None if entry.slot_axis is None else int(leaf.shape[entry.slot_axis])
            held.append(Held(name, kind, slots, int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize))
    return held


def cache_slots(layer_cache: Dict[str, jax.Array]) -> Optional[int]:
    """Slots a layer's dense cache holds a row (stacked or not); None for a
    layer whose whole cache is a recurrent state, and for a layer that caches
    nothing (an empty dict: no sequence mixer)."""
    return next((leaf.slots for leaf in describe(layer_cache) if leaf.slots is not None), None)


def cacheless(cache: Any) -> int:
    """Layers of a per-layer cache list that cache nothing: an empty dict, a
    layer without a sequence mixer (``make_kv_cache``). 0 for a stacked cache."""
    return sum(1 for layer in cache if isinstance(layer, dict) and not layer) if isinstance(cache, (list, tuple)) else 0


def ring(kind: str) -> str:
    """The account's key, and the refusal's, for the part of ``kind`` that
    lies in layers whose slot axis is shorter than the row's."""
    return kind + " ring"


def _key(leaf: Held, slots: int) -> str:
    return ring(leaf.kind) if leaf.slots is not None and leaf.slots < slots else leaf.kind


def cache_bytes(cache: Any, slots: int) -> Counter:
    """Bytes of a cache pytree by kind (a key of ``KINDS``), the ring part of
    a kind apart under ``ring(kind)``: the layers that hold fewer than the
    row's ``slots``. The values sum to ``ops/paged_kv.py::kv_bytes(cache)``;
    a kind the cache does not hold reads 0."""
    held: Counter = Counter()
    for leaf in describe(cache):
        held[_key(leaf, slots)] += leaf.bytes
    return held


# the rollout paths that write a layer's cache at any slot of a row, a row at a time
PATHS = ("slot_refill", "engine", "prefix_cache", "speculative")

_NO_POOLED = "it holds K and V a slot and no compressed keys, which fill by each row's own position"
_NO_INDEX = "it scores, selects from and moves no index keys, which ride on a latent's slots"
_REFILL_CONV = "ops/slot_refill.py::SlotState (train.continuous_batching) holds K and V a slot and would refill a slot over the conv window another row left, which nothing resets"
_ENGINE_CONV = "the engine/ slots (paged cache) hold K and V blocks and no rows of a conv's window, which have no slot axis to page"
_PREFIX_CONV = "the engine's prefix cache shares K and V blocks; a conv's window after a prefix has no snapshot at a block boundary to share"
_REWIND_CONV = "ops/speculative.py rewinds K and V to the accepted length; the conv window has moved on past the rejected tokens and keeps no older rows to go back to"
_REFILL_STATE = "ops/slot_refill.py::SlotState (train.continuous_batching) holds K and V a slot and would refill a slot over another row's recurrent state"
_ENGINE_STATE = "the engine/ slots (paged cache) hold K and V blocks and no recurrent state"
_PREFIX_STATE = "the engine's prefix cache shares K and V blocks; a recurrent state has no snapshot at a block boundary to share"
_REWIND_STATE = "ops/speculative.py rewinds K and V to the accepted length and cannot rewind a recurrent state"

# what each path cannot hold: (path, kind) -> (why, the ROADMAP.md queue-2 item that lifts it). A pair without a row
# is held, and a kind's ring is held where the kind is unless `ring(kind)` has a row of its own (speculation's verify
# writes a ring of K and V: `CausalTransformer._ring_plan`)
REFUSED = {
    ("slot_refill", RECURRENT): (_REFILL_STATE, "B7b"),
    ("slot_refill", LINEAR): (_REFILL_STATE, "B7b"),
    ("slot_refill", CONV): (_REFILL_CONV, "B7b"),
    ("slot_refill", POOLED): (_NO_POOLED, "B8c"),
    ("slot_refill", LATENT): ("ops/slot_refill.py::SlotState refills a slot at its own depth, a [B] vector of cache indices, and its span prefill attends over the cache's per-head K and V", "B4b"),
    ("slot_refill", INDEX): (_NO_INDEX, "B8c"),
    ("slot_refill", ring(KV)): ("ops/slot_refill.py refills one slot's row at its own depth, a [B] vector of cache indices", "B3c"),
    ("engine", RECURRENT): (_ENGINE_STATE, "B7b"),
    ("engine", LINEAR): (_ENGINE_STATE, "B7b"),
    ("engine", CONV): (_ENGINE_CONV, "B7b"),
    ("engine", POOLED): (_NO_POOLED, "B8c"),
    ("engine", LATENT): ("the engine/ block pool and its paged kernels (ops/paged_attention.py, ops/paged_prefill.py) hold and read per-head K and V blocks", "B4a"),
    ("engine", INDEX): (_NO_INDEX, "B8c"),
    ("engine", ring(KV)): ("the engine/ block tables map every slot of a row to a block and the allocator frees none before the row ends", "B3c"),
    ("prefix_cache", RECURRENT): (_PREFIX_STATE, "B7c"),
    ("prefix_cache", LINEAR): (_PREFIX_STATE, "B7c"),
    ("prefix_cache", CONV): (_PREFIX_CONV, "B7c"),
    ("prefix_cache", POOLED): (_NO_POOLED, "B8c"),
    ("prefix_cache", LATENT): ("the engine's prefix cache shares per-head K and V blocks", "B4a"),
    ("prefix_cache", INDEX): (_NO_INDEX, "B8c"),
    ("prefix_cache", ring(KV)): ("the engine's prefix cache shares a prompt's blocks from slot 0, which a ring has overwritten", "B3c"),
    ("speculative", RECURRENT): (_REWIND_STATE, "B7c"),
    ("speculative", LINEAR): (_REWIND_STATE, "B7c"),
    ("speculative", CONV): (_REWIND_CONV, "B7c"),
    ("speculative", POOLED): (_NO_POOLED, "B8c"),
    ("speculative", LATENT): ("ops/speculative.py verifies and rewinds rows at their own accepted lengths, a [B] vector of cache indices, over per-head K and V", "B4b"),
    ("speculative", INDEX): (_NO_INDEX, "B8c"),
}


def refuse(cache: Any, path: str, slots: int) -> None:
    """Called where each of ``PATHS`` builds its state, on the cache pytree
    (arrays or shapes) it was given for a row of ``slots``: a cache that holds
    what ``REFUSED`` says the path cannot stops there, in words built from what
    was found, rather than be dropped or written as if it were K and V."""
    if path not in PATHS:
        raise ValueError(f"unknown rollout path '{path}' ({' | '.join(PATHS)})")
    found: Dict[Tuple[str, int], set] = {}  # (kind, its ring's slots or 0) -> the leaves' names
    for leaf in describe(cache):
        key = _key(leaf, slots)
        if (path, leaf.kind) in REFUSED or (path, key) in REFUSED:
            found.setdefault((leaf.kind, leaf.slots if key != leaf.kind else 0), set()).add(leaf.name)
    if not found:
        return
    reasons = []
    for (kind, short), names in sorted(found.items(), key=lambda row: (list(KINDS).index(row[0][0]), row[0][1])):
        why, item = REFUSED.get((path, kind)) or REFUSED[path, ring(kind)]
        where = f" in a ring of {short} slots for a row of {slots}" if short else ""
        reasons.append(f"{KINDS[kind]}{where} (leaves {sorted(names)}): {why} (ROADMAP.md queue 2, {item})")
    raise NotImplementedError(f"{path} does not support a model whose cache holds {'; and '.join(reasons)}; use the plain sampler")
