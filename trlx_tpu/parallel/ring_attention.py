"""Ring attention: exact causal attention over a ``sequence``-sharded mesh axis.

Long-context / context parallelism is a first-class capability here, unlike the
reference, whose only sequence story is Megatron SP (activations gathered
before the heads, ``trlx/models/modeling_nemo_ilql.py:672-677``) with sequence
length capped by config (SURVEY.md §5 "Long-context"). Ring attention removes
the cap: each device holds one ``T/n`` chunk of Q/K/V, K/V chunks rotate around
the ring via ``lax.ppermute`` over ICI, and the online-softmax accumulator
combines per-chunk ``(out, lse)`` pairs — peak memory per device stays
O(T/n · d) while the math is bit-for-bit the full-sequence softmax (up to f32
rounding).

**Causal load balance — zigzag placement.** With contiguous chunks the causal
mask is a wall-clock disaster: device 0's queries see one chunk, device n−1's
see all n, and since ring steps are lockstep, every step costs as much as its
busiest device — the causal 2× FLOP saving evaporates. Zigzag placement fixes
this: the sequence is split into 2n half-chunks and device i holds halves
``i`` and ``2n−1−i``, so every device owns one early and one late span and
per-step work is near-uniform (see :func:`ring_schedule_work` for the
schedule model; the ring tests assert the balance). The permutation is a pair
of gathers around the attention call — O(T·H·D) bandwidth, negligible next to
the O(T²·D/n) attention at ring-scale sequence lengths.

**Forward**: n ring steps; per step, one flash-attention kernel call per
(local-half × visiting-half) pair with slot offsets selecting global
positions; fully-future pairs cost ~nothing (the kernel's k-block loop
collapses to zero iterations).

**Backward (custom VJP)**: one ring sweep carrying ``(k, v, mask, dk, dv)``;
each step runs the *fused* dq+dk+dv kernel
(``trlx_tpu/ops/flash_attention.py``) using the global logsumexp saved from
the forward — after n rotations every dk/dv accumulator is back on its home
device, complete. XLA overlaps each ppermute with the next step's kernels
since the Python loop is unrolled.

**ALiBi** is supported: global token positions (cumsum of the mask, computed
before sharding) ride the ring alongside K/V, and the kernel applies the
per-head slope from true positions — left-padded prompts included.
"""

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from trlx_tpu.ops.flash_attention import (
    NEG_INF,
    flash_attention,
    flash_attention_bwd_chunk,
)


def _combine(out_a, lse_a, out_b, lse_b):
    """Merge two normalized partial-softmax results via their logsumexps.

    out/lse shapes: [B, T, H, D] / [B, H, T]. Rows masked everywhere carry the
    ``NEG_INF`` sentinel and zero output on both sides, which this preserves.
    """
    m = jnp.maximum(lse_a, lse_b)
    w_a = jnp.where(lse_a > 0.5 * NEG_INF, jnp.exp(lse_a - m), 0.0)
    w_b = jnp.where(lse_b > 0.5 * NEG_INF, jnp.exp(lse_b - m), 0.0)
    denom = w_a + w_b
    safe = jnp.where(denom > 0.0, denom, 1.0)
    lse = jnp.where(denom > 0.0, m + jnp.log(safe), NEG_INF)
    wa = (w_a / safe).transpose(0, 2, 1)[..., None]  # [B, T, H, 1]
    wb = (w_b / safe).transpose(0, 2, 1)[..., None]
    out = out_a * wa + out_b * wb
    return out, lse


def zigzag_order(T: int, n: int) -> np.ndarray:
    """Global→zigzag gather indices: device i's shard holds half-chunks
    ``i`` and ``2n−1−i`` of the 2n-way split."""
    half = T // (2 * n)
    order = []
    for i in range(n):
        order.extend(range(i * half, (i + 1) * half))
        order.extend(range((2 * n - 1 - i) * half, (2 * n - i) * half))
    return np.asarray(order, np.int32)


def ring_schedule_work(n: int, placement: str) -> Tuple[List[float], float, float]:
    """Analytic causal-work schedule: per-ring-step wall cost (max over
    devices, in units of one full chunk-pair attention), total wall, and
    total useful work. The imbalance the zigzag placement removes is
    ``total_wall / (total_work / n)`` → ~2 for contiguous, →1 for zigzag."""

    def segs(dev):
        if placement == "contiguous":
            return [(dev, 1.0)]  # (offset in chunk units, length in chunks)
        return [(dev * 0.5, 0.5), ((2 * n - 1 - dev) * 0.5, 0.5)]

    def pair_cost(qoff, qlen, koff, klen):
        # visible fraction of the (qlen × klen) tile under k_slot <= q_slot
        q_lo, q_hi = qoff, qoff + qlen
        k_lo, k_hi = koff, koff + klen
        if k_hi <= q_lo:
            return qlen * klen  # fully past: dense
        if k_lo >= q_hi:
            return 0.0  # fully future: skipped
        return 0.5 * qlen * klen  # diagonal: half-causal

    per_step, total_work = [], 0.0
    for s in range(n):
        costs = []
        for dev in range(n):
            src = (dev - s) % n
            c = sum(
                pair_cost(qo, ql, ko, kl)
                for qo, ql in segs(dev)
                for ko, kl in segs(src)
            )
            costs.append(c)
        per_step.append(max(costs))
        total_work += sum(costs)
    return per_step, sum(per_step), total_work


def _make_ring_fn(axis, n, causal, alibi, zigzag, sm_scale, block_q, block_k, interpret, window=None):
    """Build the per-shard ring function (a custom-VJP closure)."""

    def segments(dev, Tl):
        """Local (start, length, global_slot_offset) spans of this shard."""
        if not zigzag:
            return [(0, Tl, dev * Tl)]
        half = Tl // 2
        return [(0, half, dev * half), (half, half, (2 * n - 1 - dev) * half)]

    def rotate(perm, *arrays):
        return tuple(jax.lax.ppermute(a, axis, perm) for a in arrays)

    @jax.custom_vjp
    def ring(q, k, v, key_mask, qpos, kpos, slopes):
        out, _ = _ring_fwd_impl(q, k, v, key_mask, qpos, kpos, slopes)
        return out

    def _ring_fwd_impl(q, k, v, key_mask, qpos, kpos, slopes):
        idx = jax.lax.axis_index(axis)
        B, Tl, H, D = q.shape
        perm = [(j, (j + 1) % n) for j in range(n)]
        q_segs = segments(idx, Tl)

        outs = [jnp.zeros((B, ql, H, D), jnp.float32) for _, ql, _ in q_segs]
        lses = [jnp.full((B, H, ql), NEG_INF, jnp.float32) for _, ql, _ in q_segs]
        kc, vc, mc, kpc = k, v, key_mask, kpos
        for s in range(n):
            src = (idx - s) % n
            for qi, (qs, ql, qoff) in enumerate(q_segs):
                for ks, kl, koff in segments(src, Tl):
                    o_s, l_s = flash_attention(
                        q[:, qs : qs + ql],
                        kc[:, ks : ks + kl],
                        vc[:, ks : ks + kl],
                        mc[:, ks : ks + kl],
                        causal=causal,
                        sm_scale=sm_scale,
                        q_offset=qoff,
                        k_offset=koff,
                        q_positions=qpos[:, qs : qs + ql] if alibi else None,
                        k_positions=kpc[:, ks : ks + kl] if alibi else None,
                        alibi_slopes=slopes if alibi else None,
                        block_q=block_q,
                        block_k=block_k,
                        interpret=interpret,
                        return_lse=True,
                        window=window,
                    )
                    outs[qi], lses[qi] = _combine(
                        outs[qi], lses[qi], o_s.astype(jnp.float32), l_s
                    )
            if s != n - 1:
                kc, vc, mc = rotate(perm, kc, vc, mc)
                if alibi:
                    (kpc,) = rotate(perm, kpc)
        out = jnp.concatenate(outs, axis=1)
        lse = jnp.concatenate(lses, axis=2)
        return out.astype(q.dtype), lse

    def ring_fwd(q, k, v, key_mask, qpos, kpos, slopes):
        out, lse = _ring_fwd_impl(q, k, v, key_mask, qpos, kpos, slopes)
        return out, (q, k, v, key_mask, qpos, kpos, slopes, out, lse)

    def ring_bwd(res, do):
        q, k, v, key_mask, qpos, kpos, slopes, out, lse = res
        idx = jax.lax.axis_index(axis)
        B, Tl, H, D = q.shape
        perm = [(j, (j + 1) % n) for j in range(n)]
        q_segs = segments(idx, Tl)

        delta = jnp.sum(
            do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
        ).transpose(0, 2, 1)  # [B, H, Tl]

        dq = jnp.zeros_like(q, jnp.float32)
        kc, vc, mc, kpc = k, v, key_mask, kpos
        dkc = jnp.zeros_like(k, jnp.float32)
        dvc = jnp.zeros_like(v, jnp.float32)
        for s in range(n):
            src = (idx - s) % n
            for qs, ql, qoff in q_segs:
                for ks, kl, koff in segments(src, Tl):
                    dq_s, dk_s, dv_s = flash_attention_bwd_chunk(
                        q[:, qs : qs + ql],
                        kc[:, ks : ks + kl],
                        vc[:, ks : ks + kl],
                        mc[:, ks : ks + kl],
                        lse[:, :, qs : qs + ql],
                        delta[:, :, qs : qs + ql],
                        do[:, qs : qs + ql],
                        causal=causal,
                        sm_scale=sm_scale,
                        q_offset=qoff,
                        k_offset=koff,
                        q_positions=qpos[:, qs : qs + ql] if alibi else None,
                        k_positions=kpc[:, ks : ks + kl] if alibi else None,
                        alibi_slopes=slopes if alibi else None,
                        block_q=block_q,
                        block_k=block_k,
                        interpret=interpret,
                        window=window,
                    )
                    dq = dq.at[:, qs : qs + ql].add(dq_s.astype(jnp.float32))
                    dkc = dkc.at[:, ks : ks + kl].add(dk_s.astype(jnp.float32))
                    dvc = dvc.at[:, ks : ks + kl].add(dv_s.astype(jnp.float32))
            # rotate the kv chunk together with its gradient accumulator;
            # after the full sweep each accumulator is home and complete
            kc, vc, mc, dkc, dvc = rotate(perm, kc, vc, mc, dkc, dvc)
            if alibi:
                (kpc,) = rotate(perm, kpc)
        return (
            dq.astype(q.dtype),
            dkc.astype(k.dtype),
            dvc.astype(v.dtype),
            jnp.zeros_like(key_mask),
            jnp.zeros_like(qpos),
            jnp.zeros_like(kpos),
            jnp.zeros_like(slopes),
        )

    ring.defvjp(ring_fwd, ring_bwd)
    return ring


def ring_flash_attention(
    q: jax.Array,  # [B, T, H, D] global (sequence-sharded or shardable)
    k: jax.Array,  # [B, T, H, D]
    v: jax.Array,  # [B, T, H, D]
    key_mask: jax.Array,  # [B, T]
    mesh: Mesh,
    *,
    axis: str = "sequence",
    causal: bool = True,
    sm_scale: Optional[float] = None,
    q_positions: Optional[jax.Array] = None,  # [B, T] (alibi)
    k_positions: Optional[jax.Array] = None,  # [B, T] (alibi)
    alibi_slopes: Optional[jax.Array] = None,  # [H]
    placement: str = "auto",  # auto | zigzag | contiguous
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,  # sliding-window width (slot distance)
) -> jax.Array:
    """Exact attention with K/V rotating over the ``axis`` mesh ring.

    T must be divisible by ``mesh.shape[axis]``. Falls back to a single flash
    call when the axis has size 1. Differentiable (custom ring VJP).

    ``placement="auto"`` uses zigzag half-chunk placement whenever it pays
    (causal, T divisible by 2n) and contiguous otherwise.
    """
    n = mesh.shape[axis]
    if n == 1:
        return flash_attention(
            q, k, v, key_mask,
            causal=causal, sm_scale=sm_scale,
            q_positions=q_positions, k_positions=k_positions,
            alibi_slopes=alibi_slopes,
            block_q=block_q, block_k=block_k, interpret=interpret,
            window=window,
        )
    B, T, H, D = q.shape
    if T % n:
        raise ValueError(f"sequence length {T} not divisible by ring size {n}")
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    alibi = alibi_slopes is not None

    if placement == "auto":
        placement = "zigzag" if causal and T % (2 * n) == 0 else "contiguous"
    if placement == "zigzag" and T % (2 * n):
        raise ValueError(f"zigzag needs T divisible by 2n={2 * n}, got T={T}")
    zigzag = placement == "zigzag"

    if alibi:
        if q_positions is None or k_positions is None:
            raise ValueError("alibi ring attention needs q_positions/k_positions")
        qpos, kpos = q_positions.astype(jnp.int32), k_positions.astype(jnp.int32)
        slopes = alibi_slopes.astype(jnp.float32)
    else:
        qpos = jnp.zeros((B, T), jnp.int32)
        kpos = qpos
        slopes = jnp.zeros((H,), jnp.float32)

    if zigzag:
        order = jnp.asarray(zigzag_order(T, n))
        inverse = jnp.asarray(np.argsort(zigzag_order(T, n)))
        q, k, v = (jnp.take(x, order, axis=1) for x in (q, k, v))
        key_mask = jnp.take(key_mask, order, axis=1)
        qpos = jnp.take(qpos, order, axis=1)
        kpos = jnp.take(kpos, order, axis=1)

    ring = _make_ring_fn(
        axis, n, causal, alibi, zigzag, sm_scale, block_q, block_k, interpret,
        window,
    )
    # fully manual: GSPMD cannot partition the Mosaic kernels inside the
    # ring, so every mesh axis is spelled out — sequence over the ring axis,
    # batch and heads as attention_shard_axes lays them, the rest replicated
    from trlx_tpu.parallel.sharding import attention_shard_axes

    batch_axes, head_axis = attention_shard_axes(mesh, k.shape)
    shard = P(batch_axes, axis, head_axis, None)
    rows = P(batch_axes, axis)
    f = jax.shard_map(
        ring,
        mesh=mesh,
        in_specs=(shard, shard, shard, rows, rows, rows, P(head_axis)),
        out_specs=shard,
        check_vma=False,
    )
    out = f(q, k, v, key_mask, qpos, kpos, slopes)
    if zigzag:
        out = jnp.take(out, inverse, axis=1)
    return out
