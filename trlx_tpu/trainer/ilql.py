"""ILQL trainer: offline RL from reward-labeled samples.

Behavioral parity target: ``AccelerateILQLTrainer`` + module-level
``make_experience`` (``trlx/trainer/accelerate_ilql_trainer.py:30-250``):

- ``make_experience`` tokenizes dialogues, builds per-token action/state
  indices (actions at output-token positions − 1, matching the causal shift),
  normalizes returns across the dataset, and puts the scalar return on the
  final action token;
- the loss runs the backbone once, gathers hidden states at action/state
  positions, applies V/Q/target-Q heads on the *gathered* positions only
  (the reference's ``ILQLHeads.forward`` index-select,
  ``trlx/models/modeling_ilql.py:160-180``), and feeds ``ILQLConfig.loss``;
- target-Q heads Polyak-sync every ``steps_for_target_q_sync`` optimizer
  steps (``:136-138``);
- generation reshapes sampling logits on device to
  ``log π + β·(min target-Q − V)`` with top-k masking, via the
  ``adjust_logits`` hook of the jitted sampler (reference custom ``generate``,
  ``modeling_ilql.py:246-317``).
"""

from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.tokenizer import Tokenizer
from trlx_tpu.models.heads import sync_target_q_params
from trlx_tpu.models.ilql import ILQLConfig, batched_index_select
from trlx_tpu.pipeline.offline_pipeline import (
    ILQLRolloutStorage,
    ILQLSeq2SeqRolloutStorage,
    tokenize_dialogue,
)
from trlx_tpu.trainer import register_trainer
from trlx_tpu.trainer.base import TPUBaseTrainer
from trlx_tpu.utils import logging
from trlx_tpu.utils.stats import logprobs_of_labels  # noqa: F401 (parity surface)

logger = logging.get_logger(__name__)


# samples per pipelined tokenization chunk: large enough that the worker's
# per-chunk overhead is noise, small enough that index-building overlaps a
# meaningful fraction of the tokenization tail
_TOKENIZE_CHUNK = 64


def _fold_tokenized(
    samples: List[Union[str, List[str]]],
    tokenizer: Optional[Tokenizer],
    max_length: int,
    pipeline_depth: int,
    fold,
    chunk_size: int = _TOKENIZE_CHUNK,
) -> None:
    """Feed ``fold`` tokenized sample chunks in order.

    With ``pipeline_depth`` > 0 and a tokenizer, chunks tokenize on a
    :class:`~trlx_tpu.pipeline.rollout_pipeline.RolloutPipeline` worker while
    ``fold`` (the per-sample index/reward shaping) drains earlier chunks on
    the calling thread — the offline twin of the PPO generation/reward
    overlap. One worker + ordered drain ⇒ output identical to the serial
    path, element for element."""
    if tokenizer is None:
        fold(list(samples))  # already tokenized
        return
    # `> 0` (not truthiness): any non-positive depth means serial, matching
    # PPO's gate — a -1 "disable" value must not reach RolloutPipeline
    if pipeline_depth > 0 and len(samples) > chunk_size:
        from trlx_tpu.pipeline.rollout_pipeline import RolloutPipeline

        with RolloutPipeline(
            depth=pipeline_depth, finalize=fold, name="ilql_tokenize"
        ) as pipe:
            for start in range(0, len(samples), chunk_size):
                part = samples[start : start + chunk_size]
                pipe.submit(
                    lambda part=part: [
                        tokenize_dialogue(s, tokenizer, max_length) for s in part
                    ]
                )
        return
    fold([tokenize_dialogue(s, tokenizer, max_length) for s in samples])


def _causal_sample_arrays(sample) -> tuple:
    """Per-sample causal index math: (input_ids, actions_ixs, states_ixs,
    dones) — shared by the serial and pipelined paths of
    :func:`make_experience`."""
    length = 0
    input_ids = np.array([t for m in sample for t in m.tokens], dtype=np.int32)
    actions_ixs = []
    for dm in sample:
        if dm.is_output:
            # actions index into the *shifted* sequence: the action chosen
            # at state t is the token emitted at position t+1
            actions_ixs.append(
                np.arange(length - 1, length + len(dm.tokens) - 1, dtype=np.int32)
            )
        length += len(dm.tokens)
    ixs = np.concatenate(actions_ixs) if actions_ixs else np.zeros(0, np.int32)
    states_ixs = np.concatenate([ixs, np.array([length - 1], np.int32)])
    dones = np.array([1] * (len(states_ixs) - 1) + [0], dtype=np.int32)
    return input_ids, ixs, states_ixs, dones


def make_experience(
    samples: List[Union[str, List[str]]],
    rewards: List[float],
    tokenizer: Optional[Tokenizer] = None,
    max_length: int = 2048,
    verbose: bool = True,
    pipeline_depth: int = 0,
) -> ILQLRolloutStorage:
    """Tokenize samples and shape rewards into an :class:`ILQLRolloutStorage`
    (reference ``accelerate_ilql_trainer.py:30-99``). ``pipeline_depth`` > 0
    overlaps chunked tokenization (background worker) with the per-sample
    index building here — the result is identical to the serial path."""
    if verbose:
        logger.info("Collecting rollouts")

    all_input_ids = []
    all_actions_ixs = []
    all_states_ixs = []
    all_dones = []

    def fold(chunk):
        for sample in chunk:
            input_ids, ixs, states_ixs, dones = _causal_sample_arrays(sample)
            all_input_ids.append(input_ids)
            all_actions_ixs.append(ixs)
            all_states_ixs.append(states_ixs)
            all_dones.append(dones)

    _fold_tokenized(samples, tokenizer, max_length, pipeline_depth, fold)

    sample_lengths = np.array(list(map(len, all_input_ids)))
    output_lengths = np.array(list(map(len, all_actions_ixs)))
    prompt_lengths = sample_lengths - output_lengths
    if verbose:
        logger.info(
            "Experience string stats: "
            f"prompt {prompt_lengths.mean():.2f} ∈ [{prompt_lengths.min()}, {prompt_lengths.max()}], "
            f"output {output_lengths.mean():.2f} ∈ [{output_lengths.min()}, {output_lengths.max()}], "
            f"sample {sample_lengths.mean():.2f} ∈ [{sample_lengths.min()}, {sample_lengths.max()}]"
        )

    # dataset-level return normalization; scalar return lands on the final
    # action token (reference ``:83-89``)
    returns = np.asarray(rewards, dtype=np.float64)
    returns = returns - returns.mean()
    std = returns.std()
    if not np.isnan(std) and std > 0:
        returns = returns / (std + np.finfo(returns.dtype).eps)
    token_rewards = [np.zeros(len(ixs), np.float32) for ixs in all_actions_ixs]
    for rs, ret in zip(token_rewards, returns):
        if len(rs):
            rs[-1] = ret

    attention_mask = [np.ones(len(x), np.int32) for x in all_input_ids]
    return ILQLRolloutStorage(
        all_input_ids,
        attention_mask,
        token_rewards,
        all_states_ixs,
        all_actions_ixs,
        all_dones,
    )


def make_experience_seq2seq(
    samples: List[Union[str, List[str]]],
    rewards: List[float],
    tokenizer: Optional[Tokenizer] = None,
    max_length: int = 2048,
    verbose: bool = True,
    pipeline_depth: int = 0,
) -> ILQLSeq2SeqRolloutStorage:
    """Seq2seq variant: the prompt feeds the encoder, the output becomes the
    decoder sequence with actions/states indexed over decoder positions
    (reference ``make_experience_seq2seq``,
    ``accelerate_ilql_trainer.py:175-240``). ``pipeline_depth`` as in
    :func:`make_experience`."""
    if verbose:
        logger.info("Collecting rollouts")

    all_input_ids = []
    all_output_ids = []
    all_actions_ixs = []
    all_states_ixs = []
    all_dones = []

    def fold(chunk):
        for sample in chunk:
            prompt_tokens = [t for m in sample if not m.is_output for t in m.tokens]
            output_tokens = [t for m in sample if m.is_output for t in m.tokens]
            all_input_ids.append(np.asarray(prompt_tokens, np.int32))
            all_output_ids.append(np.asarray(output_tokens, np.int32))
            length = len(output_tokens)
            actions_ixs = np.arange(0, max(length - 1, 0), dtype=np.int32)
            states_ixs = np.concatenate(
                [actions_ixs, np.array([max(length - 1, 0)], np.int32)]
            )
            all_dones.append(np.array([1] * (len(states_ixs) - 1) + [0], np.int32))
            all_actions_ixs.append(actions_ixs)
            all_states_ixs.append(states_ixs)

    _fold_tokenized(samples, tokenizer, max_length, pipeline_depth, fold)

    returns = np.asarray(rewards, dtype=np.float64)
    returns = returns - returns.mean()
    std = returns.std()
    if not np.isnan(std) and std > 0:
        returns = returns / (std + np.finfo(returns.dtype).eps)
    token_rewards = [np.zeros(len(ixs), np.float32) for ixs in all_actions_ixs]
    for rs, ret in zip(token_rewards, returns):
        if len(rs):
            rs[-1] = ret

    attention_mask = [np.ones(len(x), np.int32) for x in all_input_ids]
    return ILQLSeq2SeqRolloutStorage(
        all_input_ids,
        attention_mask,
        all_output_ids,
        token_rewards,
        all_states_ixs,
        all_actions_ixs,
        all_dones,
    )


@register_trainer
class ILQLTrainer(TPUBaseTrainer):
    model_head = "ilql"

    def __init__(self, config: TRLConfig, **kwargs):
        super().__init__(config, **kwargs)
        if not isinstance(config.method, ILQLConfig):
            raise ValueError("config.method must be ILQLConfig")
        self.ilql: ILQLConfig = config.method
        self.store: Optional[ILQLRolloutStorage] = None
        self._sync_fn = self.programs.program(
            "sync_target_q_params", partial(sync_target_q_params, alpha=self.ilql.alpha)
        )

    def make_experience(
        self, samples, rewards, max_length: int = 2048
    ) -> None:
        # the rollout pipeline knob gates the offline overlap too: chunked
        # tokenization on a background worker, index building in the drain
        depth = int(getattr(self.config.train, "rollout_pipeline_depth", 0) or 0)
        if self.is_seq2seq:
            self.store = make_experience_seq2seq(
                samples, rewards, self.tokenizer, max_length=max_length,
                pipeline_depth=depth,
            )
        else:
            self.store = make_experience(
                samples, rewards, self.tokenizer, max_length=max_length,
                pipeline_depth=depth,
            )

    # ------------------------------------------------------------------
    # loss
    # ------------------------------------------------------------------

    def loss_fn(
        self, params: Any, batch: Dict[str, jax.Array], rng: jax.Array
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        module = self.module

        if self.is_seq2seq:
            # decoder positions carry actions/states (reference seq2seq heads
            # forward, ``modeling_ilql.py:396-427``); logits project at the
            # gathered action positions only, like the causal path below
            backbone_out = module.apply(
                {"params": params},
                batch["input_ids"],
                attention_mask=batch["attention_mask"],
                decoder_input_ids=batch["decoder_input_ids"],
                logits_span=(0, 0),
                method=type(module).backbone_forward,
            )
            action_source = batch["decoder_input_ids"]
        else:
            # logits_span=(0,0): only hidden states come back — the CE term
            # needs logits at ACTION positions only, so the vocab projection
            # runs on the gathered [B, A, E] hidden below instead of the
            # full [B, T, V] tensor (the peak-memory item at large vocab)
            backbone_out = module.apply(
                {"params": params},
                batch["input_ids"],
                attention_mask=batch["attention_mask"],
                logits_span=(0, 0),
                method=type(module).backbone_forward,
            )
            action_source = batch["input_ids"]
        hidden = backbone_out["hidden_states"]

        # pin the gathered activations to the batch layout: the
        # take_along_axis output otherwise inherits a hidden-sharded spec
        # from the backbone that GSPMD can only reconcile with the heads'
        # batch-sharded expectation by an involuntary full rematerialization
        # (replicate-then-repartition) of every gathered tensor per step
        from trlx_tpu.parallel.mesh import get_global_mesh
        from trlx_tpu.parallel.sharding import batch_spec, constrain_activation

        mesh = get_global_mesh()
        hs_actions = constrain_activation(
            batched_index_select(hidden, batch["actions_ixs"]),
            mesh, *batch_spec(3),
        )
        hs_states = constrain_activation(
            batched_index_select(hidden, batch["states_ixs"]),
            mesh, *batch_spec(3),
        )
        qs, target_qs, vs = module.apply(
            {"params": params},
            hs_actions,
            hs_states,
            method=type(module).heads_on,
        )
        logits = module.apply(
            {"params": params}, hs_actions, method=type(module).project_logits
        )
        # the action token itself = the next token after the action index
        actions = jnp.take_along_axis(
            action_source[:, 1:], batch["actions_ixs"], axis=1
        )
        return self.with_router_aux(
            self.ilql.loss(
                logits=logits,
                qs=qs,
                target_qs=target_qs,
                vs=vs,
                actions=actions,
                rewards=batch["rewards"],
                dones=batch["dones"],
            ),
            backbone_out,
        )

    def prepare_learning(self) -> None:
        self.train_dataloader = self.store.create_loader(
            self.config.train.batch_size, shuffle=True, seed=self.config.train.seed
        )
        self.n_updates_per_batch = 1
        self.total_steps = min(
            self.config.train.total_steps,
            self.config.train.epochs * len(self.train_dataloader),
        )

    def post_backward_touches_state(self, updates: int) -> bool:
        return updates % self.ilql.steps_for_target_q_sync == 0

    def post_backward_callback(self) -> None:
        if self.post_backward_touches_state(self.iter_count):
            self.state = self.state.replace(
                params=self._sync_fn(self.state.params)
            )

    # ------------------------------------------------------------------
    # advantage-reshaped sampling
    # ------------------------------------------------------------------

    def adjust_logits_fn(self, extra_kwargs: Dict[str, Any]) -> Optional[Callable]:
        """On-device: logits ← log π + β(min target-Q − V); the sampler's own
        top-k/temperature filtering then applies to the shaped logits, which
        is order-equivalent to the reference's topk-then-temperature
        (``modeling_ilql.py:280-317`` — top-k selection is invariant under
        positive temperature scaling). ``beta`` resolves per generate call,
        so overrides and eval sweeps take effect."""
        beta = float(extra_kwargs.get("beta", 1.0))

        def adjust(step_out: Dict[str, Any], logits: jax.Array) -> jax.Array:
            target_qs = step_out["target_qs"]
            if isinstance(target_qs, (tuple, list)) and len(target_qs) > 1:
                q = jnp.minimum(target_qs[0], target_qs[1])
            elif isinstance(target_qs, (tuple, list)):
                q = target_qs[0]
            else:
                q = target_qs
            v = step_out["vs"]  # [B, 1]
            adv = q.astype(jnp.float32) - v.astype(jnp.float32)
            pi_beta = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            return pi_beta + beta * adv

        return adjust
