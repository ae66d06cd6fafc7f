"""GPipe-style pipeline parallelism over stacked transformer blocks.

The reference's pipeline engine is Apex/Megatron: layers are partitioned
across PP ranks, a microbatch schedule (``fwd_bwd_function``) sends stage
activations over NCCL p2p, heads live on the last stage
(``trlx/models/modeling_nemo_ilql.py:339-366,426-442``; PP=4 for 65B,
``configs/nemo_configs/megatron_65b.yaml:50``). The TPU-native equivalent
here is the GSPMD pipelining pattern (vmap-over-stages + rotating microbatch
buffer, as in the GSPMD paper §3.3 / praxis ``LayerwiseShardablePipelined``):

- the ``scan_layers`` stacked block params ``[L, ...]`` shard their layer dim
  over the mesh's ``pipe`` axis, so each stage's devices hold only their own
  ``L/S`` blocks (the analogue of Megatron's per-rank partitions);
- one jitted program runs ``M + S - 1`` schedule ticks as a ``lax.scan``;
  each tick every stage applies its blocks to the microbatch currently
  resident on it (a ``vmap`` over the stage dim — SPMD, so all stages
  compute every tick), then the activation buffer shifts one stage down via
  ``concatenate`` along the stage dim, which XLA lowers to a collective
  permute over ``pipe`` — the NCCL send/recv of the reference, compiler-
  inserted;
- microbatches enter at stage 0 and exit at stage ``S-1``; ticks before the
  pipeline fills / after it drains process replicated filler data whose
  results are discarded (the GPipe bubble — ``(S-1)/(M+S-1)`` of the
  schedule, amortised by raising ``num_microbatches``).

Deviations from the reference, by design: embeddings and the LM/value heads
are *not* stage-local — they stay sharded over ``model``/``fsdp`` and
replicated over ``pipe`` (GSPMD places their FLOPs on all devices), so there
is no first/last-stage embedding allreduce (``modeling_nemo_ilql.py:475-477``)
and no loss broadcast from the last stage (``:479-481``): outputs exit the
pipeline globally addressable, and backward is plain autodiff through the
schedule (XLA reverses the collective permutes). KV-cache decode runs through
the same schedule with stage-resident caches and validity-guarded writes.
"""

from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh


def pick_microbatches(batch_size: int, num_stages: int, requested: int = 0) -> int:
    """Resolve the microbatch count: ``requested`` (0 = one per stage), capped
    at the batch size, reduced to the largest divisor of the batch. Warns when
    the divisor fallback inflates the pipeline bubble (``(S-1)/(M+S-1)`` of
    the schedule is filler) so a throughput cliff is diagnosable."""
    target = min(requested if requested > 0 else num_stages, batch_size)
    m = target
    while batch_size % m:
        m -= 1
    if m < target:
        from trlx_tpu.utils import logging

        logging.get_logger(__name__).warning(
            "pipe microbatches reduced %d -> %d (largest divisor of batch %d): "
            "pipeline bubble is now %d/%d of the schedule — pick a batch size "
            "divisible by the microbatch count to recover throughput",
            target, m, batch_size, num_stages - 1, m + num_stages - 1,
        )
    return m


class _TickCarry(NamedTuple):
    h: jax.Array  # [S, mb, T, E] stage-resident activations
    mask: jax.Array  # [S, mb, K] attention/slot mask riding with its microbatch
    positions: jax.Array  # [S, mb, T]
    branch: Any  # [S, mb, T, E] hydra branch-input buffer, or None
    cache: Any  # stage-resident KV cache pytree, or None


def _shift_in(buf: jax.Array, inject: jax.Array) -> jax.Array:
    """Rotate the stage buffer one stage down, injecting ``inject`` at stage
    0. The cross-stage concatenate is what XLA turns into the pipe-axis
    collective permute."""
    return jnp.concatenate([inject[None], buf[:-1]], axis=0)


def pipeline_blocks(
    stacked_params: Any,  # pytree, leaves [L, ...] (the h_scan/block stack)
    x: jax.Array,  # [B, T, E]
    mask: jax.Array,  # [B, K] key/slot mask (K == T full pass; cache slots in decode)
    positions: jax.Array,  # [B, T]
    *,
    num_stages: int,
    num_microbatches: int,
    # (mask_mb, pos_mb, cache_index_mb) -> attn inputs for one microbatch;
    # cache_index_mb is the stage's [mb] slice when cache_index is a [B]
    # vector (speculative decoding), else the scalar/None passed in
    make_attn_inputs: Callable[..., Any],
    # (layer_params, h, attn_inputs, cache_layer, cache_index_mb)
    #   -> (h, new_cache_layer, aux_stats)
    apply_block: Callable[..., Tuple[jax.Array, Any, jax.Array]],
    cache: Any = None,  # pytree, leaves [L, B, ...] (stacked KV cache) or None
    cache_index: Any = None,  # None | scalar | [B] vector (per-row depths)
    branch_at: int = -1,  # global layer idx whose INPUT feeds the hydra branch
    mesh: Optional[Mesh] = None,
    aux_init: Optional[jax.Array] = None,  # zero aux vector (defines its width)
) -> Tuple[jax.Array, Optional[jax.Array], Any, jax.Array]:
    """Run the stacked block params over ``x`` through the pipeline schedule.

    Returns ``(hidden, branch_input, new_cache, aux)`` — hidden/branch/cache
    with the same shapes/layout the unpipelined ``nn.scan`` path produces
    (tested for exact logits parity). ``aux`` is the raw SUM of each block's
    aux-statistics vector over every valid (layer, microbatch) pair: blocks
    return token-weighted sufficient statistics (see
    ``models/transformer.py::router_aux_summary``), so the caller's final
    normalization stays correctly weighted even when microbatches carry
    different amounts of padding.
    """
    L = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    S, M = num_stages, num_microbatches
    if L % S:
        raise ValueError(f"num_layers {L} not divisible by pipe stages {S}")
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by pipe microbatches {M}")
    lps, mb = L // S, B // M
    track_branch = branch_at >= 0
    if aux_init is None:
        aux_init = jnp.zeros(3, jnp.float32)

    # [L, ...] -> [S, lps, ...]: L is sharded over `pipe` with exactly lps
    # contiguous rows per shard, so this reshape is local to each device.
    params_s = jax.tree_util.tree_map(
        lambda p: p.reshape((S, lps) + p.shape[1:]), stacked_params
    )
    split = lambda a: a.reshape((M, mb) + a.shape[1:])
    # pad the input streams to M + S - 1 ticks with replicas of microbatch 0:
    # real data (no NaN hazards), results discarded by the schedule
    tk = M + S - 1
    feed = lambda a: jnp.concatenate([a, jnp.repeat(a[:1], tk - M, axis=0)], axis=0)
    xs, masks, poss = feed(split(x)), feed(split(mask)), feed(split(positions))

    cache_s = None
    if cache is not None:
        # [L, B, ...] -> [S, lps, M, mb, ...]: stage-resident, never rotated
        cache_s = jax.tree_util.tree_map(
            lambda c: c.reshape((S, lps, M, mb) + c.shape[2:]), cache
        )

    # a [B]-vector cache_index (per-row cache depths — speculative decoding)
    # is split per microbatch like the data streams; each stage selects its
    # resident microbatch's slice by m_idx, exactly as it selects the cache
    vector_ci = cache_index is not None and jnp.ndim(cache_index) > 0
    ci_split = split(jnp.asarray(cache_index)) if vector_ci else None  # [M, mb]

    def constrain(a, *spec):
        if not isinstance(a, jax.core.Tracer):
            return a
        from trlx_tpu.parallel.sharding import constrain_activation

        return constrain_activation(a, mesh, *spec)

    # the microbatch streams are sliced per tick and injected into the
    # [S, mb, ...] stage buffer (dim1 over data×fsdp); constraining them here,
    # once, hands every per-tick slice to the buffer in its final layout —
    # otherwise the split()-reshape of the batch-sharded input leaves the
    # slices in a transposed device order the partitioner can only reconcile
    # with an involuntary full rematerialization at each injection
    xs = constrain(xs, None, ("data", "fsdp"))
    masks = constrain(masks, None, ("data", "fsdp"))
    poss = constrain(poss, None, ("data", "fsdp"))

    def stage_fn(stage_params, h, mask_mb, pos_mb, branch_buf, stage_cache, m_idx, stage_idx, valid):
        """One stage: apply its ``lps`` blocks to the resident microbatch."""
        ci = cache_index
        if vector_ci:
            ci = jax.lax.dynamic_index_in_dim(ci_split, m_idx, axis=0, keepdims=False)
        aux = make_attn_inputs(mask_mb, pos_mb, ci)
        cache_m = None
        if stage_cache is not None:
            # this stage currently serves microbatch m_idx: select its cache
            cache_m = jax.tree_util.tree_map(
                lambda c: jax.lax.dynamic_index_in_dim(c, m_idx, axis=1, keepdims=False),
                stage_cache,
            )

        def layer_body(carry, inp):
            h, branch_buf, aux_sum = carry
            layer_params, cache_layer, local_idx = inp
            if track_branch:
                branch_buf = jnp.where(
                    stage_idx * lps + local_idx == branch_at, h, branch_buf
                )
            h, new_cache_layer, block_aux = apply_block(
                layer_params, h, aux, cache_layer, ci
            )
            return (h, branch_buf, aux_sum + block_aux), new_cache_layer

        (h, branch_buf, aux_sum), new_cache_m = jax.lax.scan(
            layer_body,
            (h, branch_buf, aux_init),
            (stage_params, cache_m, jnp.arange(lps)),
        )
        new_stage_cache = None
        if stage_cache is not None:
            # commit the updated cache only when this stage held real data
            updated = jax.tree_util.tree_map(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n, m_idx, axis=1),
                stage_cache,
                new_cache_m,
            )
            new_stage_cache = jax.tree_util.tree_map(
                lambda u, c: jnp.where(valid, u, c), updated, stage_cache
            )
        return h, branch_buf, new_stage_cache, aux_sum

    stages = jax.vmap(stage_fn, in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0), spmd_axis_name="pipe")
    stage_iota = jnp.arange(S)

    def tick(carry: _TickCarry, inputs):
        x_t, mask_t, pos_t, t = inputs
        h = constrain(_shift_in(carry.h, x_t), "pipe", ("data", "fsdp"))
        mk = constrain(_shift_in(carry.mask, mask_t), "pipe", ("data", "fsdp"))
        ps = constrain(_shift_in(carry.positions, pos_t), "pipe", ("data", "fsdp"))
        br = carry.branch
        if track_branch:
            br = constrain(
                _shift_in(br, jnp.zeros_like(x_t)), "pipe", ("data", "fsdp")
            )
        # stage s serves microbatch t - s (valid while 0 <= t-s < M)
        m = t - stage_iota
        valid = (m >= 0) & (m < M)
        m_idx = jnp.clip(m, 0, M - 1)
        h, br, cache_new, aux_s = stages(
            params_s, h, mk, ps, br, carry.cache, m_idx, stage_iota, valid
        )
        h = constrain(h, "pipe", ("data", "fsdp"))
        # filler ticks (invalid stage/microbatch pairs) must not contribute
        aux_t = jnp.sum(jnp.where(valid[:, None], aux_s, 0.0), axis=0)
        out = (h[-1], br[-1] if track_branch else jnp.zeros((0,), x.dtype), aux_t)
        return _TickCarry(h, mk, ps, br, cache_new), out

    zeros_buf = jnp.zeros((S, mb) + x.shape[1:], x.dtype)
    init = _TickCarry(
        h=zeros_buf,
        # all-ones masks keep the filler ticks numerically benign
        mask=jnp.ones((S, mb) + mask.shape[1:], mask.dtype),
        positions=jnp.zeros((S, mb) + positions.shape[1:], positions.dtype),
        branch=zeros_buf if track_branch else None,
        cache=cache_s,
    )
    final, (ys, brs, auxs) = jax.lax.scan(
        tick, init, (xs, masks, poss, jnp.arange(tk))
    )

    # microbatch m exits the last stage at tick m + S - 1. The exit streams
    # get the mirror treatment of the feed streams: pin the per-tick layout
    # before the slice+reshape back to [B, ...] so the drain (and its
    # autodiff transpose) reshards via cheap collectives instead of a full
    # rematerialization.
    ys = constrain(ys, None, ("data", "fsdp"))
    hidden = constrain(
        ys[S - 1 :].reshape((B,) + x.shape[1:]), ("data", "fsdp")
    )
    branch_input = None
    if track_branch:
        brs = constrain(brs, None, ("data", "fsdp"))
        branch_input = constrain(
            brs[S - 1 :].reshape((B,) + x.shape[1:]), ("data", "fsdp")
        )
    new_cache = None
    if cache is not None:
        new_cache = jax.tree_util.tree_map(
            lambda c, orig: c.reshape(orig.shape), final.cache, cache
        )
    # each valid (layer, microbatch) pair contributed its weighted statistics
    # exactly once; normalization happens in the caller (router_aux_summary)
    return hidden, branch_input, new_cache, jnp.sum(auxs, axis=0)
