"""Path-based parameter sharding rules (t5x/maxtext style).

One ordered rule table maps every parameter path in the model tree to a
``PartitionSpec`` over the ``(data, pipe, fsdp, model, sequence, expert)``
mesh:

- the **model** axis carries Megatron-style tensor parallelism — qkv/mlp-up
  kernels shard their *output* features, o/mlp-down kernels their *input*
  features, embeddings and lm head shard the vocab dim (the reference gets
  this from Apex ``ColumnParallelLinear``/``RowParallelLinear``,
  ``trlx/models/modeling_nemo_ilql.py:47-99``);
- the **fsdp** axis shards the remaining large dim of each kernel — the GSPMD
  equivalent of DeepSpeed ZeRO-3 parameter sharding
  (``configs/accelerate/zero3.yaml``), with XLA inserting the all-gathers;
- small tensors (norms, biases of row-parallel layers) replicate.

Rules apply to *paths*, so the same table covers the backbone, value heads,
Q heads, and any future module that follows the naming convention.
"""

import functools
import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Ordered (path regex, spec) rules; first match wins. Paths are joined with
# "/" and include every key from the root of the param tree.
_RULES: Tuple[Tuple[str, P], ...] = (
    # attention + mlp column-parallel (output features on `model`)
    (r".*/(q_proj|k_proj|v_proj|gate_proj|up_proj)/kernel$", P("fsdp", "model")),
    (r".*/(q_proj|k_proj|v_proj|gate_proj|up_proj)/bias$", P("model")),
    # row-parallel (input features on `model`); bias replicated
    (r".*/(o_proj|down_proj)/kernel$", P("model", "fsdp")),
    (r".*/(o_proj|down_proj)/bias$", P(None)),
    # latent attention: the down-projections to a latent shard their input
    # over fsdp and leave the (small, normed) latent whole; the up-projections
    # from it are column-parallel over the heads
    (r".*/(q_a_proj|kv_a_proj)/kernel$", P("fsdp", None)),
    (r".*/(q_b_proj|kv_b_proj)/kernel$", P("fsdp", "model")),
    # mixture-of-experts MLP: expert dim over `expert` (EP), per-expert
    # matmul dims over fsdp/model exactly like the dense column/row split;
    # the router is tiny and replicates
    (r".*/mlp/w_(gate|up)$", P("expert", "fsdp", "model")),
    (r".*/mlp/w_down$", P("expert", "model", "fsdp")),
    (r".*/mlp/router/kernel$", P(None)),
    # vocab-parallel embedding (Megatron-style: vocab over model×fsdp, embed
    # replicated — lookups then yield cleanly batch-sharded activations; an
    # embed-dim-sharded table instead forces a GSPMD involuntary
    # replicate-and-repartition on every lookup output). Deliberate
    # trade-off: when the vocab doesn't divide the axes (gpt2's prime-ish
    # 50257) the table replicates rather than falling back to embed-dim
    # sharding — the indivisible-vocab families top out ~1.5B params
    # (≤0.5GB table), where replication is cheap and the lookup-layout win
    # is measured; every 6B+ family (llama/neox/bloom/opt/gptj) divides.
    (r".*/wte/embedding$", P(("model", "fsdp"), None)),
    (r".*/wpe/embedding$", P(None, None)),
    (r".*/lm_head/kernel$", P("fsdp", "model")),
    (r".*/lm_head/bias$", P("model")),
    # Mamba-2 mixer: its projections shard over fsdp alone. in_proj's output
    # is five segments (z | x | B | C | dt) of a head-and-group structure that
    # a `model` split would cut across; heads are not tensor-parallel yet
    (r".*/mixer/in_proj/kernel$", P("fsdp", None)),
    (r".*/mixer/out_proj/kernel$", P(None, "fsdp")),
    # MLP heads (value / Q): column-parallel in, row-parallel out
    (r".*/in_proj/kernel$", P("fsdp", "model")),
    (r".*/in_proj/bias$", P("model")),
    (r".*/out_proj/kernel$", P("model", None)),
    (r".*/out_proj/bias$", P(None)),
    # everything else (norm scales/biases, odd singletons): replicated
    (r".*", P()),
)


def _axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):  # combined axes, e.g. ("model", "fsdp")
        size = 1
        for axis in name:
            size *= mesh.shape[axis]
        return size
    return mesh.shape[name]


def param_spec_for_path(
    path: str, shape: Tuple[int, ...], mesh: Optional[Mesh] = None
) -> P:
    """Resolve the PartitionSpec for a parameter path.

    With a ``mesh``, each dim keeps the longest prefix of its axis group that
    divides it (:func:`fit_spec`) — e.g. a 50257 vocab over ``('model',
    'fsdp')`` replicates (odd vocab), while a vocab divisible by ``model``
    but not ``model×fsdp`` still shards over ``model`` — so sharding never
    fails on awkward dims and XLA still shards everything that divides.
    """
    for pattern, spec in _RULES:
        if re.match(pattern, path):
            break
    partitions = tuple(spec)
    if "/h_scan/" in path or path.startswith("h_scan/"):
        # scan_layers layout: a leading layer dim precedes every rule's dims
        # (stacked blocks); the layer axis shards over `pipe` — with PP>1
        # each stage's devices hold only their own blocks (the reference's
        # per-stage Megatron partitions, ``modeling_nemo_ilql.py:219-250``);
        # at pipe=1 the axis is size 1 and the spec is a no-op
        partitions = ("pipe",) + partitions
    partitions = partitions[: len(shape)]
    if mesh is not None:
        fitted = fit_spec(mesh, shape, partitions)
        # diagnosis for silently-replicated LARGE params: a dim that sheds
        # its whole (present, >1-sized) axis group costs real memory —
        # activation constraints go through fit_spec directly and stay
        # silent (there a dropped group just skips the constraint)
        if int(np.prod(shape)) * 4 >= _REPLICATE_WARN_BYTES:
            for dim, axis, kept in zip(shape, partitions, tuple(fitted)):
                if axis is None or kept is not None:
                    continue
                names = axis if isinstance(axis, tuple) else (axis,)
                present = tuple(n for n in names if n in mesh.shape)
                group = 1
                for n in present:
                    group *= mesh.shape[n]
                if group > 1:
                    _warn_dropped_axis_group(path, tuple(shape), dim, present, group)
        return fitted
    partitions = partitions + (None,) * (len(shape) - len(partitions))
    return P(*partitions)


def path_keys(key_path) -> Tuple[str, ...]:
    """jax key-path → tuple of key strings (shared by the rule matcher here
    and the structural optimizer-state matcher in ``trainer/base.py``)."""
    parts = []
    for k in key_path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return tuple(parts)


def _path_str(key_path) -> str:
    return "/".join(path_keys(key_path))


def param_specs(params: Any, mesh: Optional[Mesh] = None) -> Any:
    """PartitionSpec pytree matching ``params``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = [
        param_spec_for_path(_path_str(key_path), np.shape(leaf), mesh)
        for key_path, leaf in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, specs)


def param_shardings(params: Any, mesh: Mesh) -> Any:
    """NamedSharding pytree matching ``params``."""
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        param_specs(params, mesh),
        is_leaf=lambda x: isinstance(x, P),
    )


def _stage_global(
    x: Any, sharding: NamedSharding, staged: list, reland: bool = False
) -> jax.Array:
    """One leaf of :func:`put_global`: ``device_put`` when fully addressable,
    else the callback path with the staged (host-provenance) buffer appended
    to ``staged`` for the caller's :func:`_land_staged` sync+delete.

    ``reland=True`` forces the copy protocol on the fully-addressable branch
    too: CPU ``device_put`` of a host numpy array can alias the host buffer
    zero-copy, and a leaf that will be DONATED into a cached executable must
    be a fresh XLA-owned buffer (the restore heap-corruption hazard —
    ``utils/checkpoint.py::restore_state``). Plain placement (params built
    on device, non-donated batches) skips the copy.

    Multihost ``jax.device_put`` of host data onto a non-fully-addressable
    sharding inserts a cross-process value-equality check implemented as a
    psum — which the CPU collective backend rejects, and which is redundant
    here: every caller places host values all processes computed
    identically (SPMD host code, same seed/config). The callback path
    assembles each process's addressable shards directly — no collective,
    identical result, and the single-process behavior stays plain
    ``device_put``."""
    import jax.numpy as jnp

    if sharding.is_fully_addressable:
        out = jax.device_put(x, sharding)
        if not reland:
            return out
        staged.append(out)
        return jnp.copy(out)

    arr = np.asarray(x)
    buf = jax.make_array_from_callback(arr.shape, sharding, lambda idx: arr[idx])
    # callback buffers are host-provenance: donated into an executable
    # deserialized from the persistent compile cache they corrupt the heap
    # (the hazard utils/checkpoint.py::restore_state and resilience/
    # elastic.py re-land against). shard_params output IS donated into the
    # train step, so re-land here too; the copy is placement-time cost for
    # params and a minor per-batch cost for multihost shard_batch.
    staged.append(buf)
    return jnp.copy(buf)


def _land_staged(out: Any, staged: list) -> None:
    """ONE device sync for a whole placed tree, then free the staged
    buffers. The copies must have landed before their sources are deleted,
    but syncing per leaf would serialize transfers the runtime pipelines —
    a k-leaf batch pays one barrier, not k (non-array leaves in ``out`` are
    ignored by ``jax.block_until_ready``)."""
    if staged:
        jax.block_until_ready(out)
        for buf in staged:
            buf.delete()


def put_global(x: Any, sharding: NamedSharding, reland: bool = False) -> jax.Array:
    """``device_put`` that also works when ``sharding`` spans processes
    (see :func:`_stage_global`; ``reland`` for leaves headed into donating
    executables). Single-leaf entry — tree placement goes through
    :func:`shard_params`/:func:`shard_batch`, which batch the device sync
    across leaves."""
    staged: list = []
    out = _stage_global(x, sharding, staged, reland=reland)
    _land_staged(out, staged)
    return out


def shard_params(params: Any, mesh: Mesh) -> Any:
    """Place a parameter pytree onto the mesh per the rule table."""
    staged: list = []
    out = jax.tree_util.tree_map(
        lambda x, s: _stage_global(x, s, staged), params, param_shardings(params, mesh)
    )
    _land_staged(out, staged)
    return out


# Params at or above this size (bytes, assuming 4 B/element — specs see only
# shapes, not dtypes) get a diagnosis line when a dim sheds its entire axis
# group; smaller ones replicate silently (cheap and usually deliberate).
# Scoped to *params* (``param_spec_for_path``): for activation constraints
# the same drop means the constraint is skipped to preserve layout freedom
# (``constrain_activation``'s no-op path), not that anything replicates.
_REPLICATE_WARN_BYTES = 8 << 20


@functools.lru_cache(maxsize=None)
def _warn_dropped_axis_group(path, shape, dim, names, group) -> None:
    """Warn ONCE per (param, shape, axes) signature: the divisibility fit
    silently drops *every* axis of the group, so a large param replicates —
    up to ``group``× the memory and none of the sharding the rule table
    intended. Same warn-once contract as
    ``models/transformer.py::_warn_indivisible_experts``."""
    from trlx_tpu.utils import logging

    logging.get_logger(__name__).warning(
        "param %s of shape %s (>= %d MiB assuming 4 B/elem): the %d-sized dim "
        "is divisible by no prefix of mesh axes %s (combined size %d) — the "
        "dim replicates instead of sharding; resize the dim or the mesh axes "
        "to recover it",
        path, shape, _REPLICATE_WARN_BYTES >> 20, dim, names, group,
    )


def fit_spec(mesh: Mesh, shape: Tuple[int, ...], spec: Tuple[Any, ...]) -> P:
    """Fit a PartitionSpec to a concrete shape: per dim, keep the longest
    prefix of the axis group whose product divides the dim (``None`` when no
    present axis divides).

    Sharding constraints written for the general case meet awkward concrete
    dims — a microbatch of 1, a 6-wide head dim on a 4-way axis group. Padding
    a dim onto an axis it doesn't divide gives every consumer a
    differently-padded layout, and each reshard between them becomes a GSPMD
    involuntary full rematerialization; dropping just the non-dividing suffix
    keeps whatever sharding still fits. Size-1 axes that divide are KEPT —
    they are sharding no-ops, but retaining them keeps specs stable across
    mesh sizes (the rule table reads the same at pipe=1 and pipe=4).
    """
    if len(spec) > len(shape):
        raise ValueError(
            f"spec {tuple(spec)} has more entries than array rank {len(shape)}"
        )
    out = []
    for dim, axis in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axis is None:
            out.append(None)
            continue
        names = axis if isinstance(axis, tuple) else (axis,)
        keep: list = []
        size = 1
        for n in names:
            if n not in mesh.shape:
                continue  # absent axis contributes size 1 — skip, don't emit
            s = mesh.shape[n]
            if dim % (size * s):
                break
            keep.append(n)
            size *= s
        if keep:
            out.append(tuple(keep) if len(keep) > 1 else keep[0])
        else:
            out.append(None)
    return P(*out)


def attention_shard_axes(mesh: Mesh, kv_shape: Tuple[int, ...]):
    """``(batch_axes, head_axis)`` for running an attention kernel per shard
    inside a fully manual ``shard_map`` (GSPMD cannot partition a Mosaic
    kernel): batch over ``data`` x ``fsdp`` and heads over ``model`` — the
    layouts the q/k/v projections already produce — each kept only as far
    as it divides. Fitted on the ``[B, S, KV, D]`` key tensor: attention is
    independent per (row, head) and GQA groups are contiguous, so query
    heads may shard exactly when the (smaller) kv-head count does."""
    batch_axes, _, head_axis, _ = fit_spec(
        mesh, kv_shape, (("data", "fsdp"), None, "model", None)
    )
    return batch_axes, head_axis


def spec_to_jsonable(spec: P) -> list:
    """A PartitionSpec as JSON-safe nested lists (``None`` | axis name |
    list of names per dim) — the checkpoint topology manifest's per-leaf
    spec record (``trlx_tpu/resilience/elastic.py``)."""
    out = []
    for axis in tuple(spec):
        if axis is None:
            out.append(None)
        elif isinstance(axis, tuple):
            out.append([str(a) for a in axis])
        else:
            out.append(str(axis))
    return out


def spec_shards(mesh: Mesh, spec: P) -> int:
    """Total ways ``spec`` splits an array on ``mesh`` (1 = pure no-op)."""
    total = 1
    for axis in spec:
        total *= _axis_size(mesh, axis)
    return total


def constrain_activation(a: jax.Array, mesh: Optional[Mesh], *spec) -> jax.Array:
    """``with_sharding_constraint`` with the :func:`fit_spec` guard — the one
    helper behind every activation-layout pin (decode embedding, pipeline
    feed/drain streams, MoE dispatch). No-op without a mesh or when the
    fitted spec shards nothing (a no-op constraint would still force full
    replication rather than preserve layout freedom)."""
    if mesh is None:
        return a
    fitted = fit_spec(mesh, a.shape, spec)
    if spec_shards(mesh, fitted) == 1:
        return a
    return jax.lax.with_sharding_constraint(a, NamedSharding(mesh, fitted))


def batch_spec(ndim: int = 2, sequence_sharded: bool = False) -> P:
    """Batch arrays shard their leading dim over the combined data axes
    (``data`` × ``fsdp`` — FSDP is data parallelism with sharded state);
    optionally the second (sequence) dim over ``sequence``."""
    rest: Tuple[Optional[str], ...] = ("sequence",) if sequence_sharded else (None,)
    rest = rest + (None,) * (ndim - 2)
    return P(("data", "fsdp"), *rest[: max(ndim - 1, 0)])


def shard_batch(batch: Any, mesh: Mesh, sequence_sharded: bool = False) -> Any:
    """Place host batch arrays (numpy) onto the mesh, sharded over data axes.

    Leading dims must be divisible by ``data*fsdp`` (collators guarantee this
    by construction: batch sizes are multiples of the data-axes product).
    Non-array leaves (strings etc.) pass through untouched.
    """

    staged: list = []

    def put(x):
        if not hasattr(x, "ndim") or x.ndim == 0:
            return x
        dp = mesh.shape["data"] * mesh.shape["fsdp"]
        if x.shape[0] % dp != 0:
            spec = P()
        else:
            spec = batch_spec(x.ndim, sequence_sharded)
        return _stage_global(x, NamedSharding(mesh, spec), staged)

    out = jax.tree_util.tree_map(put, batch)
    _land_staged(out, staged)
    return out
