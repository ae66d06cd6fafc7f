"""PPO method: KL controllers, GAE, and the clipped PPO objective — pure JAX.

Behavioral parity targets in the reference:
- ``AdaptiveKLController`` / ``FixedKLController`` (``trlx/models/modeling_ppo.py:34-66``)
- ``PPOConfig.get_advantages_and_returns`` (``modeling_ppo.py:134-170``) —
  here a reverse ``lax.scan`` instead of a Python loop over T, so it traces
  into one fused XLA op.
- ``PPOConfig.loss`` (``modeling_ppo.py:172-233``) — clipped policy + clipped
  value loss with masked means and the same stats keys (approx-KL k3
  estimator, clipfracs, padding percentage).
"""

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.data.method_configs import MethodConfig, register_method
from trlx_tpu.observability.dynamics import loss_sketches
from trlx_tpu.utils.stats import get_tensor_stats, whiten
from trlx_tpu.utils import flatten_dict


class AdaptiveKLController:
    """Adaptive KL coefficient from Ziegler et al. (1909.08593 §2.2).

    β is multiplied by ``1 + clip(KL/target - 1, ±0.2) · n/horizon`` after
    each round of rollouts. Host-side scalar state, folded into the compiled
    step as an argument (so updating it never triggers a recompile).

    A non-finite ``current_kl`` (one bad batch) is *skipped* rather than
    folded in — multiplying by NaN would poison ``self.value`` forever, and
    β reaches every subsequent reward via ``kl_penalty_rewards``. Skips are
    counted in :attr:`skipped` and surfaced as the ``health/kl_ctl_skips``
    gauge (trainer/ppo.py ``post_backward_callback``).
    """

    def __init__(self, init_kl_coef: float, target: float, horizon: int):
        self.value = float(init_kl_coef)
        self.target = target
        self.horizon = horizon
        self.skipped = 0

    def update(self, current_kl: float, n_steps: int) -> None:
        if not np.isfinite(current_kl):
            self.skipped += 1
            return
        proportional_error = float(np.clip(current_kl / self.target - 1, -0.2, 0.2))
        self.value *= 1 + proportional_error * n_steps / self.horizon


class FixedKLController:
    """Constant KL coefficient."""

    def __init__(self, kl_coef: float):
        self.value = float(kl_coef)

    def update(self, current_kl: float, n_steps: int) -> None:
        pass


@dataclass
@register_method("PPOConfig")
class PPOConfig(MethodConfig):
    """Hyperparameters of PPO (field-compatible with the reference's
    ``PPOConfig``, ``trlx/models/modeling_ppo.py:74-133``).

    :param ppo_epochs: inner optimization epochs per rollout batch
    :param num_rollouts: experiences to collect before each learning phase
    :param chunk_size: rollout generation batch size
    :param init_kl_coef: initial β of the KL penalty vs the frozen reference
    :param target: adaptive-KL target (None → fixed controller)
    :param horizon: adaptive-KL horizon
    :param gamma: discount
    :param lam: GAE λ
    :param cliprange: PPO ratio clip ε
    :param cliprange_value: value clip range
    :param vf_coef: value-loss coefficient
    :param scale_reward: "running" | "ref" | None/"ignored"
    :param ref_mean/ref_std: fixed scaling moments for ``scale_reward="ref"``
    :param cliprange_reward: clip of environment reward
    :param iw_correction: off-policy importance-weight correction for
        async/disaggregated collection (docs/ASYNC_RL.md). ``"off"``
        (default — the loss is byte-for-byte the serial objective) or
        ``"clip"``: the policy-gradient term is multiplied per token by the
        truncated behavior ratio ``min(exp(old_logprobs −
        behavior_logprobs), iw_clip)``. ``old_logprobs`` are the proximal
        anchor (the scoring forward under the actor's newest params at
        chunk completion); ``behavior_logprobs`` are the sampler's exact
        per-token logprobs, which with in-flight mid-rollout weight sync
        come from a *mixture* of param versions — the ratio corrects the
        proximal/behavior mismatch, truncation bounds its variance
        (V-trace/TIS-style; PipelineRL arxiv 2509.19128).
    :param iw_clip: truncation bound of the behavior ratio.
    :param gen_kwargs: sampling kwargs for rollouts/eval
    :param gen_experience_kwargs: optional distinct sampling kwargs for rollouts
    """

    name: str = "PPOConfig"
    ppo_epochs: int = 4
    num_rollouts: int = 128
    chunk_size: int = 128
    init_kl_coef: float = 0.05
    target: Optional[float] = 6.0
    horizon: int = 10000
    gamma: float = 1.0
    lam: float = 0.95
    cliprange: float = 0.2
    cliprange_value: float = 0.2
    vf_coef: float = 1.0
    scale_reward: Optional[str] = None
    ref_mean: Optional[float] = None
    ref_std: Optional[float] = None
    cliprange_reward: float = 10.0
    iw_correction: str = "off"
    iw_clip: float = 2.0
    gen_kwargs: Dict[str, Any] = field(default_factory=dict)
    gen_experience_kwargs: Optional[Dict[str, Any]] = None

    def kl_controller(self):
        if self.target is None:
            return FixedKLController(self.init_kl_coef)
        return AdaptiveKLController(self.init_kl_coef, self.target, self.horizon)

    def get_advantages_and_returns(
        self,
        values: jax.Array,  # [B, R]
        rewards: jax.Array,  # [B, R]
        mask: Optional[jax.Array] = None,  # [B, R] response mask
        use_whitening: bool = True,
    ) -> Tuple[jax.Array, jax.Array]:
        """GAE advantages and returns over the response window.

        Reverse-time ``lax.scan``:
            δ_t = r_t + γ V_{t+1} - V_t;  A_t = δ_t + γλ A_{t+1}.
        Positions beyond a sample's true response end must carry zero
        rewards/values (enforced by ``mask`` upstream) so padding contributes
        nothing — the reference instead slices ragged per-sample tensors
        (``accelerate_ppo_trainer.py:450-455``); fixed [B, R] blocks + masks is
        the shape-stable TPU redesign.
        """
        values = values.astype(jnp.float32)
        rewards = rewards.astype(jnp.float32)
        next_values = jnp.concatenate(
            [values[:, 1:], jnp.zeros_like(values[:, :1])], axis=1
        )
        deltas = rewards + self.gamma * next_values - values  # [B, R]

        def backward(lastgaelam, delta_t):
            adv = delta_t + self.gamma * self.lam * lastgaelam
            return adv, adv

        _, adv_rev = jax.lax.scan(
            backward,
            jnp.zeros(values.shape[0], dtype=jnp.float32),
            jnp.flip(deltas, axis=1).T,  # scan over time-major reversed
        )
        advantages = jnp.flip(adv_rev.T, axis=1)
        returns = advantages + values
        if use_whitening:
            advantages = whiten(advantages, mask)
        # returns is stop-gradient'd alongside advantages: it is the value
        # loss's regression TARGET, not a prediction. In the trainer it is
        # built from batch constants (rollout values + rewards) so no
        # parameter gradient reaches it there either way — the stop makes
        # the no-leak property local to this function instead of an
        # accident of the call site (pinned in tests/test_ppo_loss.py).
        return (
            jax.lax.stop_gradient(advantages),
            jax.lax.stop_gradient(returns),
        )

    def loss(
        self,
        logprobs: jax.Array,  # [B, R] new per-token logprobs
        values: jax.Array,  # [B, R] new value predictions
        old_logprobs: jax.Array,  # [B, R] behavior-policy logprobs
        old_values: jax.Array,  # [B, R]
        advantages: jax.Array,  # [B, R]
        returns: jax.Array,  # [B, R]
        mask: jax.Array,  # [B, R] 1 on real response tokens
        behavior_logprobs: Optional[jax.Array] = None,  # [B, R] sampler logprobs
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Clipped-ratio policy loss + clipped value loss; masked sums / n.

        ``behavior_logprobs`` (async collection, ``iw_correction: clip``
        only) multiplies the pg term by the truncated proximal/behavior
        ratio — ``None`` keeps the serial objective byte-for-byte."""
        mask = mask.astype(jnp.float32)
        logprobs = logprobs.astype(jnp.float32)
        values = values.astype(jnp.float32)
        n = jnp.maximum(mask.sum(), 1.0)

        values_clipped = jnp.clip(
            values, old_values - self.cliprange_value, old_values + self.cliprange_value
        )
        vf_loss1 = jnp.square(values - returns)
        vf_loss2 = jnp.square(values_clipped - returns)
        vf_loss = 0.5 * jnp.sum(jnp.maximum(vf_loss1, vf_loss2) * mask) / n
        vf_clipfrac = jnp.sum((vf_loss2 > vf_loss1).astype(jnp.float32) * mask) / n

        log_ratio = (logprobs - old_logprobs) * mask
        ratio = jnp.exp(log_ratio)
        # k3 KL estimator (Schulman): E[(r - 1) - log r]
        approx_kl = jax.lax.stop_gradient(jnp.mean((ratio - 1) - log_ratio))

        pg_loss1 = -advantages * ratio
        pg_loss2 = -advantages * jnp.clip(ratio, 1.0 - self.cliprange, 1.0 + self.cliprange)
        iw_stats = {}
        if behavior_logprobs is not None and self.iw_correction != "off":
            rho, iw_stats = iw_weights(
                old_logprobs, behavior_logprobs, mask, self.iw_clip, n
            )
            pg_loss1 = pg_loss1 * rho
            pg_loss2 = pg_loss2 * rho
        pg_loss = jnp.sum(jnp.maximum(pg_loss1, pg_loss2) * mask) / n
        pg_clipfrac = jnp.sum((pg_loss2 > pg_loss1).astype(jnp.float32) * mask) / n

        loss = pg_loss + self.vf_coef * vf_loss

        dist = {}
        if self.dist_sketches:
            # stop-gradient'd histograms of the loss's own intermediates
            # (observability/dynamics.py) — ride the stats fetch, feed
            # nothing back, so the objective is bit-identical either way
            dist = loss_sketches(
                {
                    "log_ratio": (log_ratio, mask),
                    "kl": ((ratio - 1) - log_ratio, mask),
                    "advantages": (advantages, mask),
                    "value_error": (values - returns, mask),
                }
            )

        stats = dict(
            **iw_stats,
            **dist,
            losses=dict(total_loss=loss, policy_loss=pg_loss, value_loss=vf_loss),
            values=dict(
                get_tensor_stats(values, mask, n),
                values_error=jnp.sum(jnp.square((values - returns) * mask)) / n,
                clipfrac=vf_clipfrac,
            ),
            old_values=get_tensor_stats(old_values, mask, n),
            returns=get_tensor_stats(returns, mask, n),
            policy=dict(approx_kl=approx_kl, clipfrac=pg_clipfrac),
            ratio=jnp.sum(ratio * mask) / n,
            padding_percentage=1.0 - n / mask.size,
        )
        return loss, flatten_dict(stats)


def iw_weights(
    old_logprobs: jax.Array,  # [B, R] proximal-anchor logprobs (scoring fwd)
    behavior_logprobs: jax.Array,  # [B, R] sampler's exact behavior logprobs
    mask: jax.Array,  # [B, R] float response mask
    clip: float,
    n: jax.Array,  # masked token count
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Truncated per-token importance weights ``min(exp(old − behavior),
    clip)`` for off-policy (async/stale) samples, with their diagnostics.
    Shared by the PPO and GRPO losses (docs/ASYNC_RL.md "IW correction")."""
    log_rho = (
        old_logprobs.astype(jnp.float32) - behavior_logprobs.astype(jnp.float32)
    ) * mask
    raw = jnp.exp(log_rho)
    rho = jax.lax.stop_gradient(jnp.minimum(raw, clip))
    stats = {
        "iw": dict(
            rho_mean=jnp.sum(rho * mask) / n,
            rho_clipfrac=jnp.sum((raw > clip).astype(jnp.float32) * mask) / n,
        )
    }
    return rho, stats


def kl_penalty_rewards(
    logprobs: jax.Array,  # [B, R] policy logprobs of sampled tokens
    ref_logprobs: jax.Array,  # [B, R] reference logprobs of the same tokens
    response_mask: jax.Array,  # [B, R]
    scores: jax.Array,  # [B] scalar task rewards
    kl_coef: jax.Array,  # scalar β
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Per-token rewards = −β·(logπ − logπ_ref), with the scalar task score
    added at each sample's final response token.

    Returns ``(rewards [B, R], (mean_kl, mean_kl_per_sequence))``:
    ``mean_kl`` is the per-token mean of the k3 estimator over the whole
    [B, R] block — exactly what the reference feeds the adaptive KL
    controller (``accelerate_ppo_trainer.py:431-461``); the per-sequence
    mean (sum over tokens, mean over samples) is reported in stats.
    """
    mask = response_mask.astype(jnp.float32)
    log_ratio = (logprobs - ref_logprobs) * mask
    rewards = -kl_coef * log_ratio
    # index of last real token per row: sum(mask)-1 (clipped for empty rows)
    ends = jnp.maximum(mask.sum(axis=1).astype(jnp.int32) - 1, 0)
    rewards = rewards.at[jnp.arange(rewards.shape[0]), ends].add(scores)
    ratio = jnp.exp(log_ratio)
    k3 = (ratio - 1) - log_ratio
    mean_kl = jnp.mean(k3)  # per-token mean (controller input)
    mean_kl_per_seq = jnp.mean(jnp.sum(k3 * mask, axis=1))
    return rewards * mask, (mean_kl, mean_kl_per_seq)


def kl_penalty_rewards_np(logprobs, ref_logprobs, response_mask, scores, kl_coef):
    """Host (numpy) twin of :func:`kl_penalty_rewards` — same math on the
    already-fetched [B, R] arrays. The reward assembly depends on the
    host-side ``reward_fn`` scores, so computing it here lets the scoring
    forward be dispatched *before* the host scores exist, collapsing the
    rollout loop to a single device→host sync per batch."""
    import numpy as np

    mask = np.asarray(response_mask, np.float32)
    log_ratio = (np.asarray(logprobs) - np.asarray(ref_logprobs)) * mask
    rewards = -float(kl_coef) * log_ratio
    ends = np.maximum(mask.sum(axis=1).astype(np.int32) - 1, 0)
    rewards[np.arange(rewards.shape[0]), ends] += np.asarray(scores, np.float32)
    k3 = (np.exp(log_ratio) - 1) - log_ratio
    mean_kl = float(k3.mean())
    mean_kl_per_seq = float((k3 * mask).sum(axis=1).mean())
    return rewards * mask, (mean_kl, mean_kl_per_seq)
