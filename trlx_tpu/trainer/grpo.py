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

from time import perf_counter
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.grpo_types import GRPORLElement
from trlx_tpu.models.grpo import GRPOConfig, group_advantages_np
from trlx_tpu.pipeline import BasePipeline
from trlx_tpu.pipeline.grpo_pipeline import GRPORolloutStorage
from trlx_tpu.trainer import register_trainer
from trlx_tpu.trainer.ppo import PPOTrainer
from trlx_tpu.utils import infinite_loader, logging, to_host
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

    def add_prompt_pipeline(self, pipeline: BasePipeline) -> None:
        # one loader row fans out into group_size rollout rows
        method: GRPOConfig = self.config.method
        loader = pipeline.create_loader(
            max(method.chunk_size // method.group_size, 1),
            shuffle=True,
            seed=self.config.train.seed,
        )
        # same prompt-prefetch seam as PPO (GRPO's make_experience is still
        # serial — prefetch only overlaps collation, not reward scoring);
        # the chunk counter lets an emergency resume replay the stream
        self.prompt_iterator = self._count_prompt_chunks(
            infinite_loader(self._maybe_prefetch_prompts(loader))
        )

    # scoring reuses PPOTrainer._get_score_fn, which adapts to the head-less
    # policy (no value output, branch params bound at the tree root)

    def post_backward_callback(self) -> None:
        # GRPO's KL coefficient (method.beta) is fixed in-loss — no adaptive
        # controller to update (PPO's kl_ctl stays at its init value, unused)
        pass

    def _extra_checkpoint_state(self) -> Dict[str, Any]:
        # PPO's extra state minus the adaptive-KL coefficient (fixed in-loss)
        extra = super()._extra_checkpoint_state()
        extra.pop("kl_ctl_value", None)
        return extra

    # the scoring-forward dispatch (async copies, recompile watchdog) is
    # PPOTrainer._dispatch_score — shared with the chunked PPO device stage
    # and the continuous-batching group flush

    def _grpo_score_batch(
        self,
        prompt_ids: np.ndarray,  # [B, P] left-padded, group-contiguous rows
        prompt_mask: np.ndarray,
        response_tokens: np.ndarray,  # [B, N]
        response_mask: np.ndarray,
        elements: list,
        agg: Dict[str, Any],
    ) -> None:
        """Score + store one group-contiguous batch — the continuous-batching
        group flush, composed from the produce/finalize halves the serial
        chunk loop and the async actor/learner split also use (produce runs
        on the actor, finalize on the learner)."""
        chunk = self._grpo_chunk_produce(
            prompt_ids, prompt_mask, response_tokens, response_mask
        )
        self._grpo_chunk_finalize(chunk, elements, agg)

    def _grpo_chunk_produce(
        self,
        prompt_ids: np.ndarray,
        prompt_mask: np.ndarray,
        response_tokens: np.ndarray,
        response_mask: np.ndarray,
        score_out=None,
        params=None,
    ) -> Dict[str, Any]:
        """Device+host half of one group-contiguous batch: scoring forward
        (policy + hydra ref, async copies), string decode, host reward —
        everything that needs no learner state. Pure w.r.t. its inputs, so
        it can run on an actor thread/process."""
        B, P = prompt_ids.shape
        N = int(response_tokens.shape[1])
        if score_out is None:
            score_out = self._dispatch_score(
                (B, P, N),
                np.concatenate([prompt_ids, response_tokens], axis=1),
                prompt_mask,
                response_tokens,
                response_mask,
                params=params,
            )
        samples, prompts, outputs = self.decode(
            prompt_ids, response_tokens, append_eos_token=True
        )
        with self.obs.span("reward") as reward_sp:
            scores = np.asarray(
                self.reward_fn(samples=samples, prompts=prompts, outputs=outputs),
                dtype=np.float32,
            )
        wait_t0 = perf_counter()
        host = to_host(score_out)
        score_wait_s = perf_counter() - wait_t0
        return {
            "prompt_ids": prompt_ids,
            "prompt_mask": prompt_mask,
            "response_tokens": response_tokens,
            "response_mask": response_mask,
            "scores": scores,
            "host": host,
            "score_s": reward_sp.duration,  # reward_fn's host time
            "score_wait_s": score_wait_s,  # blocked on the scoring outputs
        }

    def _grpo_chunk_finalize(
        self, chunk: Dict[str, Any], elements: list, agg: Dict[str, Any]
    ) -> None:
        """Learner-side ordered tail: reward clipping, running moments,
        group-relative advantages, KL logging, element construction."""
        with self.obs.span("collect/finalize"):
            agg["score_time_sum"] += chunk["score_s"]
            agg["blocked_s"] += chunk["score_s"] + chunk["score_wait_s"]
            method: GRPOConfig = self.config.method
            G = method.group_size
            prompt_ids = chunk["prompt_ids"]
            prompt_mask = chunk["prompt_mask"]
            response_tokens = chunk["response_tokens"]
            response_mask = chunk["response_mask"]
            scores = chunk["scores"]
            host = chunk["host"]
            B = prompt_ids.shape[0]

            clip = method.cliprange_reward
            if clip:
                scores = np.clip(scores, -clip, clip)
            self.running_moments.update(scores)  # logging only: the group
            # normalization below IS the reward scaling in GRPO
            agg["all_scores"].append(scores)
            advantages = group_advantages_np(
                scores, G, method.scale_advantage, baseline=method.baseline
            )

            # reference KL for logging (the loss recomputes it on device);
            # to_host already landed numpy arrays — no further conversion
            lp, rlp = host["logprobs"], host["ref_logprobs"]
            delta = (rlp - lp) * response_mask
            n_tok = max(response_mask.sum(), 1)
            mean_kl = float(((np.exp(delta) - delta - 1.0) * response_mask).sum() / n_tok)
            agg["kl_sum"] += mean_kl
            agg["kl_batches"] += 1

            behavior = chunk.get("behavior_logprobs")
            if method.iw_correction == "off":
                behavior = None
            for i in range(B):
                n_i = int(response_mask[i].sum())
                if n_i == 0:
                    continue
                elements.append(
                    GRPORLElement(
                        query_tensor=prompt_ids[i][prompt_mask[i] > 0],
                        response_tensor=response_tokens[i, :n_i],
                        logprobs=lp[i, :n_i],
                        ref_logprobs=rlp[i, :n_i],
                        advantage=float(advantages[i]),
                        behavior_logprobs=(
                            np.asarray(behavior[i, :n_i], np.float32)
                            if behavior is not None
                            else None
                        ),
                    )
                )

    def _grpo_collect_serial(
        self, num_rollouts: int, elements: list, agg: Dict[str, Any]
    ) -> None:
        """Chunked reference path: each prompt batch fans out into
        ``group_size`` rows, generates to the slowest row, then scores."""
        method: GRPOConfig = self.config.method
        G = method.group_size
        while len(elements) < num_rollouts:
            prompt_ids, prompt_mask = self._next_prompt_chunk(repeat=G)

            gen_time = perf_counter()
            gen_out = self.generate(prompt_ids, prompt_mask)
            agg["generate_s"] += self.last_generate_time
            # the scoring forward, dispatch to host landing; reward_fn and
            # the token copy run inside it, while the device scores
            with self.obs.span("score") as score_sp:
                # dispatch on the generation's device arrays FIRST: it needs
                # nothing from the host, so it runs while the generation
                # outputs land and reward_fn scores them
                B, P = prompt_ids.shape
                N = int(gen_out.response_tokens.shape[1])
                score_out = self._dispatch_score(
                    (B, P, N),
                    gen_out.sequences,
                    prompt_mask,
                    gen_out.response_tokens,
                    gen_out.response_mask,
                )
                host_gen = to_host(
                    {
                        "response_tokens": gen_out.response_tokens,
                        "response_mask": gen_out.response_mask,
                    }
                )
                response_tokens = host_gen["response_tokens"]
                response_mask = host_gen["response_mask"]
                agg["gen_time_sum"] += perf_counter() - gen_time
                chunk = self._grpo_chunk_produce(
                    prompt_ids, prompt_mask, response_tokens, response_mask,
                    score_out=score_out,
                )
            agg["score_span_s"] += score_sp.duration
            # slot accounting (docs/PERFORMANCE.md): this chunk's decode ran
            # max(n_i) steps over B slots — same mask-derived gauges as
            # PPO's chunked paths, so a serial-vs-CB A/B compares them
            n_per_row = response_mask.sum(axis=1)
            decode_steps = int(n_per_row.max()) if n_per_row.size else 0
            agg["decode_steps"] += decode_steps
            agg["slot_steps"] += int(response_mask.shape[0]) * decode_steps
            agg["live_slot_steps"] += int(n_per_row.sum())
            self._grpo_chunk_finalize(chunk, elements, agg)

    def _grpo_collect_continuous(
        self, num_rollouts: int, elements: list, agg: Dict[str, Any]
    ) -> None:
        """Continuous-batching collection with *group-aware* harvest: slots
        refill from the prompt queue as individual rollouts finish; a group
        becomes ready when its last member completes, and ready groups flush
        into group-contiguous score batches in completion order — the chunk
        barrier (every group waiting for the whole chunk's slowest row) is
        gone, while the group-relative advantage math is untouched."""
        from collections import deque

        if num_rollouts <= 0:
            return
        method: GRPOConfig = self.config.method
        G = method.group_size
        gen_config, extra_kwargs = self._resolve_gen_config(eval_mode=False)
        groups_per_batch = max(method.chunk_size // G, 1)
        state: Dict[str, Any] = {
            "engine": None, "supplied": 0, "processed": 0, "next_group": 0,
        }
        partial: Dict[int, list] = {}  # group id → completed members
        ready: deque = deque()  # fully-completed groups, completion order

        def fetch_chunk() -> None:
            batch = next(self.prompt_iterator)
            ids = np.repeat(np.asarray(batch["input_ids"], np.int32), G, axis=0)
            mask = np.repeat(np.asarray(batch["attention_mask"], np.int32), G, axis=0)
            keys = self._cb_chunk_keys(ids.shape[0])
            metas = [
                (state["next_group"] + r // G, r % G) for r in range(ids.shape[0])
            ]
            state["next_group"] += ids.shape[0] // G
            if state["engine"] is None:
                state["engine"] = self._cb_make_engine(
                    gen_config, extra_kwargs, ids.shape[0], ids.shape[1]
                )
            state["engine"].enqueue_prompts(ids, mask, keys, metas=metas)
            state["supplied"] += ids.shape[0]

        def flush(n_groups: int) -> None:
            rows = [
                member
                for _ in range(n_groups)
                for member in sorted(ready.popleft(), key=lambda c: c.meta[1])
            ]
            state["processed"] += len(rows)
            self._grpo_score_batch(
                np.stack([c.prompt_ids for c in rows]).astype(np.int32),
                np.stack([c.prompt_mask for c in rows]).astype(np.int32),
                np.stack([c.tokens for c in rows]).astype(np.int32),
                np.stack([c.mask for c in rows]).astype(np.int32),
                elements,
                agg,
            )

        while True:
            while (
                len(elements) + state["supplied"] - state["processed"] < num_rollouts
            ):
                fetch_chunk()
            engine = state["engine"]
            if not engine.busy:
                if ready:
                    flush(len(ready))
                if len(elements) >= num_rollouts:
                    break
                continue
            for c in engine.step():
                members = partial.setdefault(c.meta[0], [])
                members.append(c)
                if len(members) == G:
                    ready.append(partial.pop(c.meta[0]))
            while len(ready) >= groups_per_batch:
                flush(groups_per_batch)

        agg["gen_time_sum"] += engine.stats.decode_s + engine.stats.refill_s
        agg["generate_s"] += engine.stats.decode_s  # as PPO's engine path reports it
        agg["engine_stats"] = engine.stats

    def _store_element_cls(self) -> type:
        # emergency-checkpoint payload (PPOTrainer hooks): GRPO elements
        # serialize through the same field-generic code path
        return GRPORLElement

    # -- async actor/learner split (docs/ASYNC_RL.md) -------------------

    def _async_produce_chunk(self, spec, params, version, channel) -> Dict[str, Any]:
        """GRPO actor chunk: the spec's prompt batch fans out into
        ``group_size`` group-contiguous rows, generates serially under the
        adopted params, and produces the score batch. (Async GRPO keeps the
        serial generation path; the CB group-aware harvest stays on the
        single-program loop.)"""
        if bool(getattr(self.config.train, "continuous_batching", False)):
            raise NotImplementedError(
                "async_rl + train.continuous_batching is implemented for the "
                "PPO trainer only: GRPO's group-aware harvest keeps the "
                "single-program CB loop. Drop one of the two."
            )
        G = self.config.method.group_size
        prompt_ids = np.repeat(spec.prompt_ids, G, axis=0)
        prompt_mask = np.repeat(spec.prompt_mask, G, axis=0)
        gen_out = self.generate(prompt_ids, prompt_mask, params=params, rng=spec.rng)
        B, P = prompt_ids.shape
        N = int(gen_out.response_tokens.shape[1])
        score_out = self._dispatch_score(
            (B, P, N),
            gen_out.sequences,
            prompt_mask,
            gen_out.response_tokens,
            gen_out.response_mask,
            params=params,
        )
        host_gen = to_host(
            {
                "response_tokens": gen_out.response_tokens,
                "response_mask": gen_out.response_mask,
                "behavior_logprobs": gen_out.response_logprobs,
            }
        )
        chunk = self._grpo_chunk_produce(
            prompt_ids,
            prompt_mask,
            host_gen["response_tokens"],
            host_gen["response_mask"],
            score_out=score_out,
        )
        chunk["behavior_logprobs"] = np.asarray(
            host_gen["behavior_logprobs"], np.float32
        )
        return chunk

    def _collect_async_grpo(
        self, num_rollouts: int, elements: list, agg: Dict[str, Any]
    ) -> None:
        """Learner-side drain for GRPO: same ordered-finalize contract as
        the PPO collector path, with the GRPO finalize tail."""
        collector = self._ensure_async_collector()
        collector.begin_collection()
        while len(elements) < num_rollouts:
            chunk = collector.next_chunk()
            self._grpo_chunk_finalize(chunk.payload, elements, agg)
            mask = chunk.payload["response_mask"]
            n_per_row = mask.sum(axis=1)
            agg["slot_steps"] += int(mask.shape[0]) * (
                int(n_per_row.max()) if n_per_row.size else 0
            )
            agg["live_slot_steps"] += int(n_per_row.sum())
        collector.end_collection()
        agg["async_stats"] = collector.collection_stats()

    def make_experience(self, num_rollouts: int = 1024, iter_count: int = 0) -> None:
        """Collect grouped rollouts with group-relative advantages."""
        if self._consume_skip_initial_experience():
            return
        logger.info("Collecting GRPO rollouts")
        if self.prompt_iterator is None:
            raise RuntimeError("add_prompt_pipeline must be called before make_experience")

        stats: Dict[str, float] = {}
        elements: list = []
        agg: Dict[str, Any] = {
            "kl_sum": 0.0, "kl_batches": 0, "all_scores": [],
            "gen_time_sum": 0.0, "score_time_sum": 0.0,
            "slot_steps": 0, "live_slot_steps": 0,
            # fenced generate spans, score spans, decode steps, and what the
            # producing thread spent in reward_fn or waiting for scoring outputs
            "generate_s": 0.0, "score_span_s": 0.0, "decode_steps": 0,
            "blocked_s": 0.0,
        }
        self.obs.tracer.next_cycle()
        with self.obs.span("collect/experience"):
            exp_time = perf_counter()

            if bool(self.config.async_rl.enabled):
                self._collect_async_grpo(num_rollouts, elements, agg)
            elif bool(getattr(self.config.train, "continuous_batching", False)):
                self._grpo_collect_continuous(num_rollouts, elements, agg)
            else:
                self._grpo_collect_serial(num_rollouts, elements, agg)

            with self.obs.span("collect/finalize", stage="collection"):
                self.mean_kl = agg["kl_sum"] / max(agg["kl_batches"], 1)
                stats["policy/sqrt_ref_kl"] = float(np.sqrt(max(self.mean_kl, 0.0)))
                stats["time/exp_generate"] = agg["gen_time_sum"]
                stats.update(self.last_spec_stats)
                stats["time/exp_score"] = agg["score_time_sum"]
                all_scores = agg["all_scores"]
                pooled = np.concatenate(all_scores) if all_scores else np.zeros((0,), np.float32)
                stats["exp_scores/mean"] = float(pooled.mean()) if pooled.size else 0.0
                stats["exp_scores/std"] = float(pooled.std()) if pooled.size else 0.0
                if "async_stats" in agg:
                    stats.update(agg["async_stats"])
                engine_stats = agg.get("engine_stats")
                if engine_stats is not None:
                    engine_metrics = engine_stats.metrics()
                    stats.update(engine_metrics)
                    # EngineStats snapshot into the crash flight recorder (same as
                    # the PPO continuous path)
                    self.obs.flightrec.record("engine_stats", engine_metrics)
                elif agg["slot_steps"]:
                    # mask-derived slot gauges on the serial path (the CB branch
                    # reports the engine's exact counters above)
                    stats["throughput/slot_utilization"] = (
                        agg["live_slot_steps"] / agg["slot_steps"]
                    )
                    stats["rollout/padded_decode_frac"] = (
                        1.0 - agg["live_slot_steps"] / agg["slot_steps"]
                    )
                self._host_gap_t0 = perf_counter()  # the first step's gap starts here
                total = self._host_gap_t0 - exp_time
                stats["time/exp"] = total
                # the same collection keys as PPO publishes (trainer/ppo.py), each a
                # sum over the collection's chunks
                stats["time/generate"] = agg["generate_s"]
                stats["time/score"] = agg["score_span_s"]
                stats["time/reward"] = agg["score_time_sum"]
                stats["time/collect_host"] = max(
                    0.0, total - agg["generate_s"] - agg["blocked_s"]
                )
                stats["rollout/decode_steps"] = float(agg["decode_steps"])
                if agg["decode_steps"]:
                    stats["time/decode_step"] = agg["generate_s"] / agg["decode_steps"]
                self.make_experience_stats = stats
                self.tracker.log(stats, step=iter_count)

                self.store.push(elements[:num_rollouts] if num_rollouts else elements)
                if self.log_rollouts:
                    self.store.export_history(location=self.rollout_logging_dir)

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
