"""Fused Pallas learner-step kernel: GAE + whitening + clipped PPO loss.

The generation hot path went native in PRs 12/13/16; the learner hot path
stayed staged XLA: ``PPOConfig.get_advantages_and_returns`` (a reverse
``lax.scan``), ``utils/stats.py::whiten`` (two masked reduction passes),
and ``PPOConfig.loss`` (clipped pg/value terms plus a dozen masked stats)
each materialize and re-read the ``[B, R]`` response-window operands from
HBM. HEPPO-GAE (arxiv 2501.12703) makes the case that GAE is a
pipeline-friendly fusion target; this module fuses the whole chain into
ONE Pallas program: each ``[B, R]`` operand is loaded into VMEM exactly
once (its whole-operand BlockSpec is the one HBM→VMEM crossing), then the
kernel body runs the reversed GAE recurrence, the masked two-pass
mean/var whitening, and the clipped losses + clipfrac/approx-KL stats
(and the ``dist/*`` sketches, when enabled) straight-line on the resident
operands — no per-stage HBM round-trips (A/B:
``benchmarks/LOSS_KERNEL_cpu.json``).

Bit-parity is the contract, same as every kernel in this repo: the fused
program must equal the staged XLA path to the bit — loss, grads, every
stat, every sketch bin. The design rule that makes that cheap to
guarantee: the kernel body does not *reimplement* anything. It calls the
genuine ``PPOConfig.get_advantages_and_returns`` and ``PPOConfig.loss``
methods on the VMEM-resident slices (:func:`_loss_core`), so the op
sequence inside the kernel is the reference op sequence by construction —
the kernel only changes where the operands live. The backward pass is a
second Pallas program that re-assembles the operands and differentiates
the same ``_loss_core`` trace with ``jax.vjp`` (recompute-over-residuals,
the flash-attention precedent), wired through ``jax.custom_vjp``.
Gradients flow to ``logprobs`` and ``values`` only: the remaining
operands (``old_*``, ``rewards``, ``mask``, ``behavior_logprobs``) are
batch constants in the trainer — no parameter reaches them — and the
XLA path's ``stop_gradient`` on advantages (and on returns, see
``get_advantages_and_returns``) makes the GAE chain a constant w.r.t.
params there too, so declaring them non-differentiable here is exact,
not an approximation (pinned by the grad-parity sweep in
``tests/test_fused_loss.py``).

Operands enter the kernel in their ORIGINAL dtypes — the methods cast
internally (``loss`` casts logprobs/values/mask to f32 but binds
``old_values`` at its incoming precision into the clip arithmetic), and
pre-casting host-side would change those mixed-precision bits.

The kernel's grid is deliberately a SINGLE step, not a row-block
assembly loop, and that choice is the fourth documented lowering
landmine (joining the three in ``ops/paged_attention.py`` /
``ops/paged_prefill.py``): the fused chain's reductions are global over
``[B, R]`` — the GAE scan is sequential in R and the whitening moments
span the whole mask — so every row must be VMEM-resident before any
compute can start and a multi-step grid saves no VMEM; what it DOES do
is wrap the compute step in the interpreter's cond-in-grid-loop, where
XLA CPU emits some of the masked sums with a different accumulation
order than the straight-line reference program — 1-ulp drift in scalar
stats, and at some block widths the loss itself. Relatedly, parity must
be pinned jit-to-jit *with every operand passed as a runtime argument*
(how the trainer actually runs): an eager op-by-op reference drifts
1 ulp in the scalar stat epilogue (inside one compiled program XLA
contracts ``1 − n/size`` into a fused multiply-add it cannot form across
eager dispatches), and a reference that *closes over* a bf16
``old_values`` lets XLA constant-fold the ``old_values ± cliprange``
clip bounds at a different precision than the runtime bf16 arithmetic —
a 2⁻¹¹-scale shift in the value loss, far beyond reduction jitter. All
pinned by ``tests/test_fused_loss.py``.

Off-TPU the program runs under the Pallas interpreter (the kernel body
as ordinary XLA ops — what the CPU tier-1 parity suite pins).

Hardware notes: the GAE recurrence is a reversed ``lax.scan`` and the
sketches are scatter-adds — both trace into the kernel body and run under
the interpreter (the pinned tier-1 contract). Mosaic refuses the program
(``Unimplemented primitive in Pallas TPU lowering for KernelType.TC: rev``;
past ``rev`` it lowers only fori_loop-shaped scans, with no per-step
inputs or outputs), so on a TPU ``method.loss_kernel: pallas`` raises at
compile time — strict xfail in ``tests/test_aot_tpu.py`` until the body is
rewritten (``docs/PERFORMANCE.md`` "Fused learner kernels").
``block_rows`` sets the batch-axis pad granularity (keep it a multiple
of 8, the f32 sublane, on chip); the response width pads to the
128-lane multiple.

Registered in ``analysis/kernels.py::KERNEL_PARITY`` as ``fused-loss``:
graftlint's kernel-discipline pass keeps ``fused_ppo_loss`` gated through
``pallas_utils``, forbids literal ``train/loss_kernel_pallas`` stamps
(GL1002 — the twice-shipped fallback-gauge bug), and fails the tree if
the staged reference or ``tests/test_fused_loss.py`` disappears
(docs/STATIC_ANALYSIS.md).
"""

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from trlx_tpu.observability.dynamics import SKETCH_BINS
from trlx_tpu.ops.pallas_utils import (
    LANES,
    align_rows,
    resolve_interpret,
)

__all__ = [
    "LossParams",
    "loss_params_of",
    "fused_ppo_loss",
    "fused_ppo_loss_reference",
]


class LossParams(NamedTuple):
    """The hashable subset of ``PPOConfig`` the fused program closes over
    (``jax.custom_vjp`` nondiff args must hash; method objects don't)."""

    gamma: float
    lam: float
    cliprange: float
    cliprange_value: float
    vf_coef: float
    iw_correction: str
    iw_clip: float
    dist_sketches: bool


def loss_params_of(method) -> LossParams:
    """Extract :class:`LossParams` from a ``PPOConfig``-shaped method."""
    return LossParams(
        gamma=float(method.gamma),
        lam=float(method.lam),
        cliprange=float(method.cliprange),
        cliprange_value=float(method.cliprange_value),
        vf_coef=float(method.vf_coef),
        iw_correction=str(method.iw_correction),
        iw_clip=float(method.iw_clip),
        dist_sketches=bool(method.dist_sketches),
    )


@functools.lru_cache(maxsize=None)
def _method_of(p: LossParams):
    """A fresh ``PPOConfig`` carrying ``p`` — the kernel body calls the
    genuine method implementations, never a transcription of them."""
    from trlx_tpu.models.ppo import PPOConfig  # late: models import this module

    return PPOConfig(
        gamma=p.gamma,
        lam=p.lam,
        cliprange=p.cliprange,
        cliprange_value=p.cliprange_value,
        vf_coef=p.vf_coef,
        iw_correction=p.iw_correction,
        iw_clip=p.iw_clip,
        dist_sketches=p.dist_sketches,
    )


def _loss_core(p: LossParams, logprobs, values, old_logprobs, old_values,
               rewards, mask, behavior_logprobs=None):
    """The staged XLA chain, verbatim, on whatever arrays it is handed:
    GAE → whiten → clipped loss + stats. Called by the reference path on
    HBM arrays and by the kernel body on VMEM slices — one definition is
    the bit-parity argument."""
    m = _method_of(p)
    advantages, returns = m.get_advantages_and_returns(old_values, rewards, mask)
    return m.loss(
        logprobs=logprobs,
        values=values,
        old_logprobs=old_logprobs,
        old_values=old_values,
        advantages=advantages,
        returns=returns,
        mask=mask,
        behavior_logprobs=behavior_logprobs,
    )


@functools.lru_cache(maxsize=None)
def _stat_keys(p: LossParams, shapes_dtypes: tuple, use_iw: bool):
    """Discover the loss's stats-dict keys abstractly (``jax.eval_shape``
    — zero FLOPs) and split them into scalar vs histogram outputs. The
    kernel packs stats in this key order; the host wrapper unpacks in the
    same order."""
    sds = [jax.ShapeDtypeStruct(s, d) for (s, d) in shapes_dtypes]
    _, stats = jax.eval_shape(
        lambda *ops: _loss_core(p, *ops[:6], ops[6] if use_iw else None),
        *(sds[:7] if use_iw else sds[:6]),
    )
    scalar_keys = tuple(k for k, v in stats.items() if v.shape == ())
    hist_keys = tuple(k for k, v in stats.items() if v.shape == (SKETCH_BINS,))
    leftover = set(stats) - set(scalar_keys) - set(hist_keys)
    if leftover:  # a new stats shape needs an output-packing decision here
        raise ValueError(f"unpackable loss stats shapes: {sorted(leftover)}")
    return scalar_keys, hist_keys


def _fused_loss_fwd_kernel(*refs, p, B, R, n_ops, scalar_keys, hist_keys):
    # single-step grid: every [B, R] operand block is already VMEM-resident
    # (loaded from HBM exactly once by its BlockSpec — the entire point;
    # the staged path re-reads them per stage), and the whole fused chain
    # runs straight-line on the slices. See the module docstring's fourth
    # landmine for why there is deliberately NO row-block assembly loop
    # here: the chain's reductions are global over [B, R] (GAE is
    # sequential in R, the whitening moments span the whole mask), so
    # row-blocking saves no VMEM — and a multi-step grid wraps the compute
    # in the interpreter's cond-in-loop, where XLA CPU emits some masked
    # sums with a different accumulation order (1-ulp drift).
    in_refs = refs[:n_ops]
    loss_ref, sc_ref, hist_ref = refs[n_ops:]
    ops = [ref[0:B, 0:R] for ref in in_refs]
    blp = ops[6] if n_ops == 7 else None
    loss, stats = _loss_core(p, *ops[:6], blp)
    loss_ref[...] = jnp.broadcast_to(loss.astype(jnp.float32), loss_ref.shape)
    sc = jnp.stack([stats[k].astype(jnp.float32) for k in scalar_keys])
    sc_ref[...] = jnp.broadcast_to(sc[:, None], sc_ref.shape)
    if hist_keys:
        hist_ref[...] = jnp.stack(
            [stats[k].astype(jnp.float32) for k in hist_keys]
        )
    else:
        hist_ref[...] = jnp.zeros(hist_ref.shape, jnp.float32)


def _fused_loss_bwd_kernel(*refs, p, B, R, n_ops):
    in_refs = refs[:n_ops]
    g_ref = refs[n_ops]
    dlp_ref, dv_ref = refs[n_ops + 1:]
    ops = [ref[0:B, 0:R] for ref in in_refs]
    blp = ops[6] if n_ops == 7 else None

    def loss_of(lp_s, v_s):
        loss, _ = _loss_core(p, lp_s, v_s, *ops[2:6], blp)
        return loss

    # recompute-over-residuals (the flash-bwd precedent): differentiate
    # the SAME _loss_core trace the forward ran, w.r.t. the two operands
    # gradients actually reach
    _, vjp = jax.vjp(loss_of, ops[0], ops[1])
    dlp, dv = vjp(g_ref[0, 0])
    # zero-fill then sub-slice store (NOT ``.at[...].set`` — a
    # full-coverage indexed update lowers to a scatter whose empty index
    # arrays Pallas rejects as captured constants)
    dlp_ref[...] = jnp.zeros(dlp_ref.shape, dlp_ref.dtype)
    dv_ref[...] = jnp.zeros(dv_ref.shape, dv_ref.dtype)
    dlp_ref[0:B, 0:R] = dlp.astype(dlp_ref.dtype)
    dv_ref[0:B, 0:R] = dv.astype(dv_ref.dtype)


def _shapes_dtypes(operands) -> tuple:
    return tuple((x.shape, jnp.dtype(x.dtype).name) for x in operands)


def _pad_operands(operands, B_pad, R_pad):
    B, R = operands[0].shape
    return [jnp.pad(x, ((0, B_pad - B), (0, R_pad - R))) for x in operands]


def _fwd_call(p, interpret, block_rows, operands):
    B, R = operands[0].shape
    B_pad = -(-B // block_rows) * block_rows
    R_pad = align_rows(R, interpret)
    n_ops = len(operands)
    scalar_keys, hist_keys = _stat_keys(
        p, _shapes_dtypes(operands), n_ops == 7
    )
    NS, NH = len(scalar_keys), max(1, len(hist_keys))
    kernel = functools.partial(
        _fused_loss_fwd_kernel,
        p=p,
        B=B,
        R=R,
        n_ops=n_ops,
        scalar_keys=scalar_keys,
        hist_keys=hist_keys,
    )
    op_spec = pl.BlockSpec((B_pad, R_pad), lambda: (0, 0))
    out_loss, out_sc, out_h = pl.pallas_call(
        kernel,
        grid=(),
        in_specs=[op_spec] * n_ops,
        out_specs=[
            pl.BlockSpec((1, LANES), lambda: (0, 0)),
            pl.BlockSpec((NS, LANES), lambda: (0, 0)),
            pl.BlockSpec((NH, SKETCH_BINS), lambda: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((NS, LANES), jnp.float32),
            jax.ShapeDtypeStruct((NH, SKETCH_BINS), jnp.float32),
        ],
        interpret=interpret,
    )(*_pad_operands(operands, B_pad, R_pad))
    return out_loss[0, 0], out_sc[:, 0], out_h


def _bwd_call(p, interpret, block_rows, operands, g_loss):
    B, R = operands[0].shape
    B_pad = -(-B // block_rows) * block_rows
    R_pad = align_rows(R, interpret)
    n_ops = len(operands)
    kernel = functools.partial(
        _fused_loss_bwd_kernel,
        p=p,
        B=B,
        R=R,
        n_ops=n_ops,
    )
    op_spec = pl.BlockSpec((B_pad, R_pad), lambda: (0, 0))
    g = jnp.broadcast_to(
        g_loss.astype(jnp.float32).reshape(1, 1), (1, LANES)
    )
    dlp, dv = pl.pallas_call(
        kernel,
        grid=(),
        in_specs=[op_spec] * n_ops + [pl.BlockSpec((1, LANES), lambda: (0, 0))],
        out_specs=[
            pl.BlockSpec((B_pad, R_pad), lambda: (0, 0)),
            pl.BlockSpec((B_pad, R_pad), lambda: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B_pad, R_pad), jnp.float32),
            jax.ShapeDtypeStruct((B_pad, R_pad), jnp.float32),
        ],
        interpret=interpret,
    )(*_pad_operands(operands, B_pad, R_pad), g)
    return dlp[0:B, 0:R], dv[0:B, 0:R]


# --- custom_vjp pairs (fixed arity: custom_vjp has no varargs, so the
# iw-corrected seven-operand program is a sibling, not a branch) ---------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _fused_noiw(p, interpret, block_rows, lp, v, olp, ov, rw, mask):
    return _fwd_call(p, interpret, block_rows, (lp, v, olp, ov, rw, mask))


def _fused_noiw_fwd(p, interpret, block_rows, lp, v, olp, ov, rw, mask):
    res = (lp, v, olp, ov, rw, mask)
    return _fwd_call(p, interpret, block_rows, res), res


def _fused_noiw_bwd(p, interpret, block_rows, res, ct):
    lp, v = res[0], res[1]
    dlp, dv = _bwd_call(p, interpret, block_rows, res, ct[0])
    zeros = tuple(jnp.zeros_like(x) for x in res[2:])
    return (dlp.astype(lp.dtype), dv.astype(v.dtype)) + zeros


_fused_noiw.defvjp(_fused_noiw_fwd, _fused_noiw_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _fused_iw(p, interpret, block_rows, lp, v, olp, ov, rw, mask, blp):
    return _fwd_call(
        p, interpret, block_rows, (lp, v, olp, ov, rw, mask, blp)
    )


def _fused_iw_fwd(p, interpret, block_rows, lp, v, olp, ov, rw, mask, blp):
    res = (lp, v, olp, ov, rw, mask, blp)
    return _fwd_call(p, interpret, block_rows, res), res


def _fused_iw_bwd(p, interpret, block_rows, res, ct):
    lp, v = res[0], res[1]
    dlp, dv = _bwd_call(p, interpret, block_rows, res, ct[0])
    zeros = tuple(jnp.zeros_like(x) for x in res[2:])
    return (dlp.astype(lp.dtype), dv.astype(v.dtype)) + zeros


_fused_iw.defvjp(_fused_iw_fwd, _fused_iw_bwd)


# --- host entry points --------------------------------------------------


def fused_ppo_loss_reference(
    method,
    logprobs: jax.Array,  # [B, R]
    values: jax.Array,  # [B, R]
    old_logprobs: jax.Array,  # [B, R]
    old_values: jax.Array,  # [B, R]
    rewards: jax.Array,  # [B, R]
    mask: jax.Array,  # [B, R] float response mask
    behavior_logprobs: Optional[jax.Array] = None,
) -> Tuple[jax.Array, dict]:
    """The staged XLA composition — GAE → whiten → loss — exactly as the
    trainer's ``loss_kernel: xla`` path runs it (the parity reference)."""
    return _loss_core(
        loss_params_of(method),
        logprobs,
        values,
        old_logprobs,
        old_values,
        rewards,
        mask,
        behavior_logprobs,
    )


def fused_ppo_loss(
    method,
    logprobs: jax.Array,  # [B, R] new per-token logprobs
    values: jax.Array,  # [B, R] new value predictions
    old_logprobs: jax.Array,  # [B, R] proximal-anchor logprobs
    old_values: jax.Array,  # [B, R] rollout values (GAE input + clip anchor)
    rewards: jax.Array,  # [B, R] per-token KL-penalty rewards
    mask: jax.Array,  # [B, R] 1.0 on real response tokens
    behavior_logprobs: Optional[jax.Array] = None,
    *,
    interpret: Optional[bool] = None,
    block_rows: int = 8,
) -> Tuple[jax.Array, dict]:
    """GAE + whitening + clipped PPO loss as one fused Pallas program.

    Returns ``(loss, stats)`` bit-identical — loss, grads (via the paired
    backward kernel), every stat, every ``dist/*`` sketch bin — to
    ``method.get_advantages_and_returns`` followed by ``method.loss``
    (pinned by ``tests/test_fused_loss.py``). Stats come back
    stop-gradient'd; gradients flow through ``loss`` to ``logprobs`` and
    ``values`` only (the rest are batch constants in the trainer).
    """
    p = loss_params_of(method)
    interpret = resolve_interpret(interpret)
    use_iw = behavior_logprobs is not None and p.iw_correction != "off"
    operands = (logprobs, values, old_logprobs, old_values, rewards, mask)
    if use_iw:
        loss, scalars, hists = _fused_iw(
            p, interpret, block_rows, *operands, behavior_logprobs
        )
    else:
        loss, scalars, hists = _fused_noiw(p, interpret, block_rows, *operands)
    scalar_keys, hist_keys = _stat_keys(
        p,
        _shapes_dtypes(operands + ((behavior_logprobs,) if use_iw else ())),
        use_iw,
    )
    stats = {}
    for idx, k in enumerate(scalar_keys):
        stats[k] = jax.lax.stop_gradient(scalars[idx])
    for idx, k in enumerate(hist_keys):
        stats[k] = jax.lax.stop_gradient(hists[idx])
    return loss, stats
