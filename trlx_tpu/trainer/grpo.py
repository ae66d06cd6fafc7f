"""GRPO trainer: group sampling, group-relative advantages, no value head.

Beyond the reference (which ships PPO/ILQL/SFT): the PPO trainer's TPU
rollout machinery — jitted KV-cache generation, the score-free scoring
forward overlapping the host reward call, the hydra frozen-reference branch
— is inherited unchanged; what changes is *what* is learned from a rollout:

- each prompt is repeated ``group_size`` times (group-contiguous rows);
- the scalar reward of each sequence is normalized within its group
  (:func:`~trlx_tpu.models.grpo.group_advantages_np`) — no values, no GAE;
- the KL penalty moves from reward shaping into the loss
  (:meth:`~trlx_tpu.models.grpo.GRPOConfig.loss`), so rewards stay pure.
"""

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.grpo_types import GRPORLElement
from trlx_tpu.models.grpo import GRPOConfig, group_advantages_np
from trlx_tpu.pipeline.grpo_pipeline import GRPORolloutStorage
from trlx_tpu.trainer import register_trainer
from trlx_tpu.trainer.ppo import PPOTrainer
from trlx_tpu.utils import logging
from trlx_tpu.utils.stats import logprobs_of_labels

logger = logging.get_logger(__name__)


@register_trainer
class GRPOTrainer(PPOTrainer):
    model_head = None  # no value function — half the trainable state

    def __init__(self, config: TRLConfig, **kwargs):
        # cheap config validation before the expensive model build
        if config.model.model_arch_type == "seq2seq":
            raise NotImplementedError("GRPO is implemented for causal LMs")
        method = config.method
        if not isinstance(method, GRPOConfig):
            raise ValueError("config.method must be GRPOConfig")
        if method.chunk_size % method.group_size:
            raise ValueError(
                f"chunk_size {method.chunk_size} must be a multiple of "
                f"group_size {method.group_size}"
            )
        from trlx_tpu.models.grpo import BASELINES

        if method.baseline not in BASELINES:
            raise ValueError(
                f"unknown method.baseline '{method.baseline}'; known: {BASELINES}"
            )
        if bool(config.async_rl.enabled) and bool(
            getattr(config.train, "continuous_batching", False)
        ):
            # fail at construction, not on the Nth actor thread after
            # max_actor_restarts respawn cycles
            raise NotImplementedError(
                "async_rl + train.continuous_batching is implemented for the "
                "PPO trainer only: GRPO's group-aware harvest keeps the "
                "single-program CB loop. Drop one of the two."
            )
        if method.baseline == "rloo":
            if method.group_size < 2:
                raise ValueError("baseline=rloo needs group_size >= 2")
            if method.scale_advantage:
                logger.warning(
                    "baseline=rloo ignores scale_advantage (RLOO is unscaled "
                    "by definition) — set method.scale_advantage: false to "
                    "silence this"
                )
        super().__init__(config, **kwargs)
        self.store = GRPORolloutStorage(self.tokenizer.pad_token_id)

    @property
    def _rollout_fanout(self) -> int:
        # one prompt becomes group_size group-contiguous rollout rows
        return self.config.method.group_size

    # rollout collection — every stage and driver — and the scoring program
    # (which adapts to the head-less policy: no value output, branch params
    # bound at the tree root) are PPOTrainer's; this fan-out and the two
    # hooks further down (_chunk_element_fn, _collection_summary) are GRPO's
    # part of it

    def post_backward_callback(self) -> None:
        # GRPO's KL coefficient (method.beta) is fixed in-loss — no adaptive
        # controller to update (PPO's kl_ctl stays at its init value, unused)
        pass

    def _extra_checkpoint_state(self) -> Dict[str, Any]:
        # PPO's extra state minus the adaptive-KL coefficient (fixed in-loss)
        extra = super()._extra_checkpoint_state()
        extra.pop("kl_ctl_value", None)
        return extra

    def _store_element_cls(self) -> type:
        # emergency-checkpoint payload (PPOTrainer hooks): GRPO elements
        # serialize through the same field-generic code path
        return GRPORLElement

    def _chunk_element_fn(
        self,
        chunk: Dict[str, Any],
        scores: np.ndarray,
        stats: Dict[str, float],
        acc: Dict[str, float],
    ):
        """Reward clipping, running moments, group-relative advantages and
        the k3 reference KL of one group-contiguous chunk."""
        method: GRPOConfig = self.config.method
        response_mask = chunk["response_mask"]
        clip = method.cliprange_reward
        if clip:
            scores = np.clip(scores, -clip, clip)
        self.running_moments.update(scores)  # logging only: the group
        # normalization below IS the reward scaling in GRPO
        acc.setdefault("all_scores", []).append(scores)
        advantages = group_advantages_np(
            scores, method.group_size, method.scale_advantage, baseline=method.baseline
        )

        # reference KL for logging (the loss recomputes it on device);
        # to_host already landed numpy arrays — no further conversion
        lp, rlp = chunk["host"]["logprobs"], chunk["host"]["ref_logprobs"]
        delta = (rlp - lp) * response_mask
        n_tok = max(response_mask.sum(), 1)
        mean_kl = float(((np.exp(delta) - delta - 1.0) * response_mask).sum() / n_tok)
        acc["kl_sum"] += mean_kl
        acc["kl_batches"] += 1

        def element(i: int, n_i: int, **common) -> GRPORLElement:
            return GRPORLElement(
                logprobs=lp[i, :n_i],
                ref_logprobs=rlp[i, :n_i],
                advantage=float(advantages[i]),
                **common,
            )

        return element

    def _collection_summary(
        self, stats: Dict[str, float], acc: Dict[str, float]
    ) -> None:
        """The k3 reference KL and the pooled (clipped) scores of the whole
        collection. The rollout health detectors are not fed: they read
        PPO's keys, and a trip writes a triage batch through PPO's
        ``_triage_extra`` forward."""
        stats["policy/sqrt_ref_kl"] = float(np.sqrt(max(self.mean_kl, 0.0)))
        all_scores = acc.get("all_scores")
        pooled = np.concatenate(all_scores) if all_scores else np.zeros((0,), np.float32)
        stats["exp_scores/mean"] = float(pooled.mean()) if pooled.size else 0.0
        stats["exp_scores/std"] = float(pooled.std()) if pooled.size else 0.0

    def loss_fn(
        self, params: Any, batch: Dict[str, jax.Array], rng: jax.Array
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Forward on query‖response then the GRPO clipped objective."""
        method: GRPOConfig = self.config.method
        queries = batch["query_tensors"]
        responses = batch["response_tensors"]
        Q, R = queries.shape[1], responses.shape[1]
        input_ids = jnp.concatenate([queries, responses], axis=1)
        attention_mask = jnp.concatenate(
            [batch["query_mask"], batch["response_mask"]], axis=1
        )
        out = self.module.apply(
            {"params": params}, input_ids, attention_mask=attention_mask,
            logits_span=(Q - 1, Q + R - 1),
        )
        logprobs = logprobs_of_labels(out["logits"], responses)
        return self.with_router_aux(
            method.loss(
                logprobs=logprobs,
                old_logprobs=batch["logprobs"],
                ref_logprobs=batch["ref_logprobs"],
                advantages=batch["advantages"],
                mask=batch["response_mask"],
                behavior_logprobs=batch.get("behavior_logprobs"),
            ),
            out,
        )
