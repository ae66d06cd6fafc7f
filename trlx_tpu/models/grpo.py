"""GRPO method: group-relative advantages + clipped objective, no value head.

Beyond the reference (trlx v0.6.0 ships PPO/ILQL/SFT only): Group Relative
Policy Optimization (Shao et al. 2024, DeepSeekMath §4.1) samples a *group*
of responses per prompt and uses the group-normalized reward as a per-sequence
advantage, dropping the value function entirely — half the trainable state
and no GAE/value-loss machinery. The KL penalty moves from reward shaping
into the loss (the unbiased k3 estimator against the frozen reference).

Plugs into the same registries the reference's methods use
(``trlx/data/method_configs.py:9-56``): ``GRPOConfig`` subclasses
:class:`~trlx_tpu.models.ppo.PPOConfig`, so the PPO trainer's rollout
machinery (jitted generation, hydra reference branch, score-free overlap)
is inherited wholesale by :class:`~trlx_tpu.trainer.grpo.GRPOTrainer`.
"""

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.data.method_configs import register_method
from trlx_tpu.models.ppo import PPOConfig
from trlx_tpu.utils import flatten_dict
from trlx_tpu.utils.stats import get_tensor_stats


BASELINES = ("group", "rloo")  # the one whitelist (trainer validation imports it)


def group_advantages_np(
    scores: np.ndarray,
    group_size: int,
    scale: bool = True,
    eps: float = 1e-6,
    baseline: str = "group",
) -> np.ndarray:
    """Per-sequence advantages from grouped rewards (host side, numpy).

    ``scores`` [B] must be laid out group-contiguously (the rollout loop
    repeats each prompt ``group_size`` times in a row). ``scale=False``
    skips the per-group std division (the "Dr. GRPO" variant, which removes
    the difficulty bias of std normalization).

    ``baseline="rloo"`` uses the leave-one-out mean of the OTHER group
    members as each sequence's baseline (REINFORCE-Leave-One-Out, Kool et
    al. 2019; Ahmadian et al. 2024) — an unbiased baseline, since a
    sequence's own reward never appears in it. Requires ``group_size >= 2``
    and ignores ``scale`` (RLOO is unscaled by definition).
    """
    if scores.shape[0] % group_size:
        raise ValueError(
            f"batch {scores.shape[0]} not divisible by group_size {group_size}"
        )
    g = scores.reshape(-1, group_size)
    if baseline == "rloo":
        if group_size < 2:
            raise ValueError("rloo baseline needs group_size >= 2")
        loo_mean = (g.sum(axis=1, keepdims=True) - g) / (group_size - 1)
        return (g - loo_mean).reshape(-1).astype(np.float32)
    if baseline != "group":
        raise ValueError(f"unknown baseline '{baseline}'; known: {BASELINES}")
    adv = g - g.mean(axis=1, keepdims=True)
    if scale:
        adv = adv / (g.std(axis=1, keepdims=True) + eps)
    return adv.reshape(-1).astype(np.float32)


@dataclass
@register_method("GRPOConfig")
class GRPOConfig(PPOConfig):
    """GRPO hyperparameters.

    Inherits the PPO sampling/rollout knobs; the value-function fields
    (``cliprange_value``, ``vf_coef``, ``gamma``, ``lam``) are unused.

    :param group_size: responses sampled per prompt; ``chunk_size`` must be
        a multiple of it.
    :param beta: coefficient of the in-loss KL penalty vs the frozen
        reference (k3 estimator) — replaces PPO's KL-shaped rewards.
    :param scale_advantage: divide group-centered rewards by the group std
        (True = original GRPO; False = Dr. GRPO).
    :param baseline: ``"group"`` (group-mean baseline, GRPO) or ``"rloo"``
        (leave-one-out mean — REINFORCE-Leave-One-Out; unbiased baseline,
        no std scaling).
    """

    name: str = "GRPOConfig"
    group_size: int = 8
    beta: float = 0.04
    scale_advantage: bool = True
    baseline: str = "group"

    def loss(
        self,
        logprobs: jax.Array,  # [B, R] current policy logprobs of response tokens
        old_logprobs: jax.Array,  # [B, R] behavior logprobs at collection time
        ref_logprobs: jax.Array,  # [B, R] frozen-reference logprobs
        advantages: jax.Array,  # [B] per-sequence group-relative advantages
        mask: jax.Array,  # [B, R] response mask
        behavior_logprobs: jax.Array = None,  # [B, R] sampler logprobs (async)
    ) -> Tuple[jax.Array, Dict[str, Any]]:
        """Clipped ratio objective with sequence-level advantages and an
        in-loss KL penalty; token-mean normalization (masked).
        ``behavior_logprobs`` (async collection, ``iw_correction: clip``)
        applies the truncated proximal/behavior importance weight to the pg
        term — ``None`` keeps the serial objective byte-for-byte."""
        from trlx_tpu.models.ppo import iw_weights

        mask = mask.astype(jnp.float32)
        n = jnp.maximum(mask.sum(), 1.0)
        adv = advantages.astype(jnp.float32)[:, None]

        log_ratio = (logprobs - old_logprobs) * mask
        ratio = jnp.exp(log_ratio)
        pg_loss1 = -adv * ratio
        pg_loss2 = -adv * jnp.clip(ratio, 1.0 - self.cliprange, 1.0 + self.cliprange)
        iw_stats = {}
        if behavior_logprobs is not None and self.iw_correction != "off":
            rho, iw_stats = iw_weights(
                old_logprobs, behavior_logprobs, mask, self.iw_clip, n
            )
            pg_loss1 = pg_loss1 * rho
            pg_loss2 = pg_loss2 * rho
        pg_loss = jnp.sum(jnp.maximum(pg_loss1, pg_loss2) * mask) / n

        # k3 KL estimator vs the frozen reference (Schulman 2020): unbiased,
        # guaranteed non-negative — exp(δ) − δ − 1 with δ = ref − current
        delta = (ref_logprobs - logprobs) * mask
        kl = jnp.sum((jnp.exp(delta) - delta - 1.0) * mask) / n

        loss = pg_loss + self.beta * kl

        approx_kl_old = 0.5 * jnp.sum(log_ratio**2) / n  # vs behavior policy
        clipfrac = jnp.sum((pg_loss2 > pg_loss1).astype(jnp.float32) * mask) / n
        dist = {}
        if self.dist_sketches:
            from trlx_tpu.observability.dynamics import loss_sketches

            # per-token ref-KL is the k3 integrand GRPO already penalizes;
            # advantages are per-sequence [B] (mask=None — every row counts)
            dist = loss_sketches(
                {
                    "log_ratio": (log_ratio, mask),
                    "ref_kl": (jnp.exp(delta) - delta - 1.0, mask),
                    "advantages": (advantages, None),
                }
            )
        stats = dict(
            **iw_stats,
            **dist,
            losses=dict(
                total_loss=loss,
                policy_loss=pg_loss,
                kl_loss=kl,
            ),
            ratio=get_tensor_stats(ratio, mask, n),
            advantages_mean=jnp.mean(adv),
            policy=dict(approx_kl=approx_kl_old, clipfrac=clipfrac, ref_kl=kl),
            padding_percentage=1.0 - n / mask.size,
        )
        return loss, flatten_dict(stats)
