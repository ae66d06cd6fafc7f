"""PPO trainer: rollout collection with KL penalty vs a frozen reference,
reward scaling, GAE + clipped-objective optimization.

Behavioral parity target: ``AcceleratePPOTrainer``
(``trlx/trainer/accelerate_ppo_trainer.py:33-489``):

- ``make_experience`` — jitted KV-cache generation, host reward scoring,
  running-moments reward scaling/clipping, a scoring forward for logprobs +
  values, a frozen-reference forward (hydra branch when
  ``num_layers_unfrozen > 0``, else a full frozen copy), per-token KL-penalty
  rewards with the task score on the final token;
- ``loss`` — GAE advantages/returns then the clipped PPO objective
  (``trlx/models/modeling_ppo.py:134-233``);
- KL controller updated post-backward, store refilled post-epoch.

TPU redesign notes: the reference's rank choreography (pad/gather to rank 0,
reward on rank 0, scatter back, ``:292-327``) collapses to device_get →
host reward fn → shard_batch, since arrays are globally sharded. All rollout
math (KL penalty, masked stats) runs on device in one jitted program per
shape bucket.
"""

import os
from collections import deque
from contextlib import ExitStack
from time import perf_counter
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.ppo_types import PPORLElement
from trlx_tpu.models.builder import hydra_ref_params
from trlx_tpu.models.ppo import PPOConfig, kl_penalty_rewards_np
from trlx_tpu.observability.dynamics import (
    SKETCH_RANGES,
    entropy_of_logits,
    loss_sketches,
    sketch_np,
)
from trlx_tpu.models.transformer import CausalTransformer
from trlx_tpu.ops.sampling import GenerationOutput, kv_slots_read, layer_extents
from trlx_tpu.parallel import shard_batch
from trlx_tpu.pipeline import BasePipeline
from trlx_tpu.pipeline.ppo_pipeline import PPORolloutStorage, length_ladder, pad_length
from trlx_tpu.trainer import register_trainer
from trlx_tpu.observability import tracing
from trlx_tpu.trainer.base import TPUBaseTrainer, attributed_between
from trlx_tpu.utils import infinite_loader, logging, to_host
from trlx_tpu.utils.stats import RunningMoments, logprobs_of_labels

logger = logging.get_logger(__name__)


def _add_times(stats: Dict[str, float], chunk_stats: Dict[str, float]) -> None:
    """Fold one chunk's stats into the collection's: ``time/*`` keys add up
    (a share of the collection's wall time is only right for a sum), any
    other key keeps the newest chunk's value."""
    for key, value in chunk_stats.items():
        if key.startswith("time/"):
            value = stats.get(key, 0.0) + value
        stats[key] = value


# The scoring forward holds float32 logits over the whole response span of
# every row of its chunk, for the policy and for the reference: 16 rows x 2048
# positions x 37,984 ids are 5 GB each, and the rows' 131,072 tokens another
# 4 GB a buffer in a dropless expert layer; neither fits one v5e beside the
# weights (compiled for a described v5e, PR 33: the program was refused). Rows
# do not interact, so a chunk of more than SCORE_MAX_TOKENS slots is scored in
# groups of rows of at most SCORE_GROUP_TOKENS slots, one after another inside
# the one program. The largest chunk of the cells that came before (64 rows x
# 640 = 40,960 slots) is under the first number and keeps its program.
# Constants with their arithmetic, not settings.
SCORE_MAX_TOKENS = 65536
SCORE_GROUP_TOKENS = 16384


def score_row_groups(rows: int, width: int) -> int:
    """How many equal groups of rows a scoring forward of ``rows x width``
    slots runs in: 1 up to ``SCORE_MAX_TOKENS`` slots, else the fewest that
    divide ``rows`` into groups of at most ``SCORE_GROUP_TOKENS`` slots (a
    row wider than that is a group of its own)."""
    if rows * width <= SCORE_MAX_TOKENS:
        return 1
    for groups in range(1, rows + 1):
        if rows % groups == 0 and (rows // groups) * width <= max(SCORE_GROUP_TOKENS, width):
            return groups
    return rows


@register_trainer
class PPOTrainer(TPUBaseTrainer):
    model_head = "value"
    # post_epoch_callback rebuilds the dataloader from the refilled store:
    # the emergency-resume fast-forward must not burn shuffle draws on it
    _fresh_loader_per_epoch = True

    def __init__(self, config: TRLConfig, **kwargs):
        super().__init__(config, **kwargs)
        method: PPOConfig = config.method
        if not isinstance(method, PPOConfig):
            raise ValueError("config.method must be PPOConfig")
        if self.reward_fn is None:
            raise ValueError("PPO requires a reward_fn")

        self.store = PPORolloutStorage(self.tokenizer.pad_token_id)
        self.kl_ctl = method.kl_controller()

        # Frozen reference for the KL penalty. With a partially-unfrozen model
        # the reference branch shares the frozen trunk and only copies the top
        # layers (hydra; reference ``modeling_ppo.py:331-427``); otherwise a
        # full frozen backbone copy (``accelerate_ppo_trainer.py:71-74``).
        # Copies are real (jnp.copy): the train step donates its input state,
        # so the snapshot must own its buffers.
        nlu = config.model.num_layers_unfrozen
        self.num_layers_unfrozen = nlu
        if self.is_seq2seq:
            from trlx_tpu.models.builder import seq2seq_hydra_ref_params
            from trlx_tpu.models.seq2seq import T5Transformer

            if nlu > 0:
                extract = lambda p: seq2seq_hydra_ref_params(p, self.tcfg, nlu)  # noqa: E731
            else:
                extract = lambda p: p["backbone"]  # noqa: E731
            self._ref_module = T5Transformer(self.tcfg)
        else:
            if nlu > 0:
                extract = lambda p: hydra_ref_params(p, self.tcfg, nlu)  # noqa: E731
            else:
                # head wrappers scope the transformer under "backbone";
                # head-less policies (GRPO) are the bare transformer tree
                extract = lambda p: p["backbone"] if "backbone" in p else p  # noqa: E731
            self._ref_module = CausalTransformer(self.tcfg)
        self.ref_params = self._ref_snapshot(extract)

        self.running_moments = RunningMoments()
        self.ref_mean: Optional[float] = method.ref_mean
        self.ref_std: Optional[float] = method.ref_std

        self.prompt_iterator = None
        self.mean_kl = 0.0
        self._score_fns: Dict[Tuple[int, int, int], Any] = {}
        self.make_experience_stats: Dict[str, float] = {}
        # query and response widths the learner's loader pads to
        self._step_ladders: Tuple[Tuple[int, ...], Tuple[int, ...]] = ((), ())

        # disaggregated async collection (trlx_tpu/async_rl/,
        # docs/ASYNC_RL.md): the collector is built lazily at the first
        # async make_experience; _async_version is the learner's update
        # clock (the weight-channel version)
        self._async = None
        self._async_version = 0

        if config.train.rollout_logging_dir is not None:
            self.log_rollouts = True
            self.setup_rollout_logging(config)
        else:
            self.log_rollouts = False

    def _ref_snapshot(self, extract):
        """Frozen-reference snapshot of (a branch of) the current params.

        Real runs take buffer-owning copies (the train step donates its
        input state, so the snapshot must not alias it), all in one program;
        a copy keeps its operand's sharding, so the branch lies as the params
        it was taken from do. Under ``abstract_init`` only shapes are
        produced — the branch extractor's slicing traces fine under
        ``eval_shape`` and an abstract trainer never executes."""
        if self.abstract_init:
            return jax.eval_shape(extract, self.state.params)

        def ref_snapshot(params):
            return jax.tree_util.tree_map(jnp.copy, extract(params))

        return self.programs.program("ref_snapshot", ref_snapshot, once=True)(self.state.params)

    # ------------------------------------------------------------------
    # rollout collection
    # ------------------------------------------------------------------

    # hook 1 of 3 (stage map below): rollout rows one prompt becomes,
    # group-contiguous
    _rollout_fanout = 1

    def add_prompt_pipeline(self, pipeline: BasePipeline) -> None:
        # one loader row fans out into _rollout_fanout rollout rows
        loader = pipeline.create_loader(
            max(self.config.method.chunk_size // self._rollout_fanout, 1),
            shuffle=True,
            seed=self.config.train.seed,
        )
        # prompt collation prefetches on a background thread when the rollout
        # pipeline is on, so chunk dispatch never stalls on next(...); the
        # chunk counter lets an emergency resume replay the stream position
        self.prompt_iterator = self._count_prompt_chunks(
            infinite_loader(self._maybe_prefetch_prompts(loader))
        )

    def _extra_checkpoint_state(self) -> Dict[str, Any]:
        return {
            "kl_ctl_value": float(self.kl_ctl.value),
            # the post-backward KL update reads mean_kl from the last
            # collection; a resumed run must apply the same update
            "mean_kl": float(self.mean_kl),
            "running_moments": {
                "mean": self.running_moments.mean,
                "std": self.running_moments.std,
                "var": self.running_moments.var,
                "count": self.running_moments.count,
            },
        }

    def _restore_extra_checkpoint_state(self, extra: Dict[str, Any]) -> None:
        if "kl_ctl_value" in extra:
            self.kl_ctl.value = float(extra["kl_ctl_value"])
        if "mean_kl" in extra:
            self.mean_kl = float(extra["mean_kl"])
        rm = extra.get("running_moments")
        if rm:
            self.running_moments.mean = rm["mean"]
            self.running_moments.std = rm["std"]
            self.running_moments.var = rm["var"]
            self.running_moments.count = rm["count"]

    # -- emergency-checkpoint payload (docs/RESILIENCE.md) --------------
    #
    # A preemption freezes the run BETWEEN two updates, usually mid-epoch:
    # the store still holds rollouts the remaining updates must train on.
    # The payload serializes them (field-generically — GRPO's element type
    # rides the same code) so the resumed run replays the exact batches an
    # uninterrupted run would, instead of re-collecting with the restored
    # policy and diverging.

    _STORE_PAYLOAD = "rollout_store.npz"

    def _store_element_cls(self) -> type:
        return PPORLElement

    def _save_emergency_payload(self, directory: str) -> None:
        import dataclasses as _dc

        arrays: Dict[str, np.ndarray] = {"count": np.asarray(len(self.store.history))}
        for i, elem in enumerate(self.store.history):
            for f in _dc.fields(elem):
                raw = getattr(elem, f.name)
                if raw is None:  # optional fields (behavior_logprobs) skip
                    continue
                value = np.asarray(raw)
                if value.dtype.kind == "V":
                    # custom float dtypes (bfloat16) round-trip through npz
                    # as raw void bytes; widen to f32 — exact, and collation
                    # casts these fields to f32 for the train batch anyway
                    value = value.astype(np.float32)
                arrays[f"{i}.{f.name}"] = value
        np.savez(os.path.join(directory, self._STORE_PAYLOAD), **arrays)

    def _restore_emergency_payload(self, directory: str) -> None:
        import dataclasses as _dc

        path = os.path.join(directory, self._STORE_PAYLOAD)
        if not os.path.exists(path):
            return
        cls = self._store_element_cls()
        names = [f.name for f in _dc.fields(cls)]
        with np.load(path) as data:
            elements = []
            for i in range(int(data["count"])):
                fields = {}
                for name in names:
                    key = f"{i}.{name}"
                    if key not in data:  # optional field saved as absent
                        continue
                    value = data[key]
                    fields[name] = value.item() if value.ndim == 0 else value
                elements.append(cls(**fields))
        self.store.clear_history()
        self.store.push(elements)
        # the initial trlx.train() collection must be skipped exactly once:
        # the uninterrupted run would be training on THESE rollouts here
        self._skip_initial_experience = True

    def setup_rollout_logging(self, config: TRLConfig) -> None:
        import os

        dir_name = config.train.rollout_logging_dir
        os.makedirs(dir_name, exist_ok=True)
        self.rollout_logging_dir = dir_name

    def _get_score_fn(self, batch_shape: Tuple[int, int, int]):
        """Jitted scoring program for a (B, P, N) shape bucket: one policy
        forward (logits + values + trunk activations) and one frozen-reference
        forward (hydra branch replay or full copy), returning per-token
        logprobs / ref logprobs / values.

        Deliberately score-free: it is dispatched the moment generation
        finishes and its outputs copy to host asynchronously, so the device
        scoring forward + transfer genuinely overlap the host-side string
        decode and ``reward_fn`` (and, with ``rollout_pipeline_depth`` > 0,
        the next chunk's generation); the KL-penalty reward assembly then
        runs on host (:func:`trlx_tpu.models.ppo.kl_penalty_rewards_np`)."""
        if batch_shape in self._score_fns:
            return self._score_fns[batch_shape]

        module = self.module
        ref_module = self._ref_module
        nlu = self.num_layers_unfrozen
        B, P, N = batch_shape

        if self.is_seq2seq:
            start_id = self.tcfg.decoder_start_token_id

            def score_fn(params, ref_params, sequences, prompt_mask, response_tokens,
                         response_mask, take=None):  # never grouped: take stays None
                # encoder side: the prompt; decoder side: teacher-forced
                # responses shifted right behind the start token (reference
                # seq2seq scoring, ``accelerate_ppo_trainer.py:369-398``)
                prompt_ids = sequences[:, :P]
                dec_in = jnp.concatenate(
                    [jnp.full((B, 1), start_id, jnp.int32), response_tokens[:, :-1]],
                    axis=1,
                )
                dec_mask = jnp.concatenate(
                    [jnp.ones((B, 1), jnp.int32), response_mask[:, :-1]], axis=1
                )
                out = module.apply(
                    {"params": params},
                    prompt_ids,
                    attention_mask=prompt_mask,
                    decoder_input_ids=dec_in,
                    decoder_attention_mask=dec_mask,
                    branch_layer=nlu if nlu > 0 else None,
                )
                # decoder position i predicts response token i directly
                logprobs = logprobs_of_labels(out["logits"], response_tokens)
                values = out["value"]

                if nlu > 0:
                    ref_out = module.apply(
                        {"params": {"backbone": ref_params}},
                        out["branch_input"],
                        nlu,
                        out["encoder_hidden"],
                        prompt_mask,
                        dec_mask,
                        method=type(module).forward_branch,
                    )
                else:
                    ref_out = ref_module.apply(
                        {"params": ref_params},
                        prompt_ids,
                        attention_mask=prompt_mask,
                        decoder_input_ids=dec_in,
                        decoder_attention_mask=dec_mask,
                    )
                ref_logprobs = logprobs_of_labels(ref_out["logits"], response_tokens)
                return {
                    "logprobs": logprobs,
                    "values": values,
                    "ref_logprobs": ref_logprobs,
                }

            fn = self.programs.program("score_fn", score_fn, batch_shape)
            self._score_fns[batch_shape] = fn
            return fn

        # head wrappers scope the transformer under "backbone"; head-less
        # policies (GRPO) are the bare transformer, so the hydra branch
        # params bind at the tree root and there is no value output
        has_value = self.model_head == "value"
        wrap_ref = (lambda p: {"backbone": p}) if self.model_head else (lambda p: p)

        def score_rows(params, ref_params, sequences, prompt_mask, response_tokens,
                       response_mask):
            full_mask = jnp.concatenate([prompt_mask, response_mask], axis=1)
            # logits at t predict token t+1: response token i lives at column
            # P+i, so its logprob/value come from position P-1+i; the vocab
            # projection is restricted to exactly that span (logits_span)
            span = (P - 1, P + N - 1)
            out = module.apply(
                {"params": params},
                sequences,
                attention_mask=full_mask,
                branch_layer=nlu if nlu > 0 else None,
                logits_span=span,
            )
            logprobs = logprobs_of_labels(out["logits"], response_tokens)

            if nlu > 0:
                ref_out = module.apply(
                    {"params": wrap_ref(ref_params)},
                    out["branch_input"],
                    nlu,
                    full_mask,
                    None,
                    span,
                    method=type(module).forward_branch,
                )
            else:
                ref_out = ref_module.apply(
                    {"params": ref_params}, sequences, attention_mask=full_mask,
                    logits_span=span,
                )
            ref_logprobs = logprobs_of_labels(ref_out["logits"], response_tokens)
            result = {"logprobs": logprobs, "ref_logprobs": ref_logprobs}
            if has_value:
                result["values"] = out["value"][:, P - 1 : P + N - 1]
            return result

        groups = score_row_groups(B, P + N)

        def score_fn(params, ref_params, *rows, take=None):
            rows = rows if take is None else group_rows(*rows, take)  # one length group of a chunk
            if groups == 1:
                return score_rows(params, ref_params, *rows)
            split = lambda a: a.reshape(groups, B // groups, *a.shape[1:])  # too long to score at once
            out = jax.lax.map(lambda g: score_rows(params, ref_params, *g), tuple(map(split, rows)))
            return jax.tree_util.tree_map(lambda a: a.reshape(B, *a.shape[2:]), out)

        fn = self.programs.program("score_fn", score_fn, batch_shape)
        self._score_fns[batch_shape] = fn
        return fn

    # The per-chunk rollout work splits into three stages with distinct
    # concurrency homes (docs/PERFORMANCE.md):
    #
    #   device   — main thread: prompt fetch, jitted generation, scoring-
    #              forward dispatch + async device→host copies;
    #   host     — worker thread when train.rollout_pipeline_depth > 0:
    #              string decode, reward_fn, landing the device arrays.
    #              Pure w.r.t. its inputs (no trainer state mutation);
    #   finalize — main thread, strictly in submission order: running-
    #              moments update (the one sequential dependency — reward
    #              scaling must fold chunks in order), then the algorithm's
    #              reward or advantage assembly and element construction.
    #
    # Four drivers run the stages (_collect_serial, _collect_pipelined,
    # _collect_continuous, _collect_async) and make_experience sums them up;
    # a subclass changes none of that. What an algorithm owns is three hooks:
    # _rollout_fanout (rows per prompt), _chunk_element_fn (scores → stored
    # elements, inside finalize) and _collection_summary (the reward and KL
    # keys of the collection's record). GRPO is exactly those three.
    #
    # Within one make_experience call the params never change, so running
    # chunk k+1's generation while chunk k's host work drains is *exactly*
    # equivalent to the serial schedule: the store is bit-identical under a
    # fixed seed (tests/test_rollout_pipeline.py pins this).

    def _dispatch_score(
        self,
        shape: Tuple[int, int, int],  # (B, P, N)
        sequences,  # [B, P+N] device rows (chunked paths) or host rows (CB)
        prompt_mask,
        response_tokens,
        response_mask,
        params=None,  # async actors score under their adopted param copy
        prompt_ids=None,  # the chunk's prompts on the host: with them its rows are scored by length
    ):
        """Dispatch the scoring forward and start its async device→host
        copies — the single home of the dispatch tail (recompile watchdog,
        async copies) shared by the chunked device stage, the continuous-
        batching group flush, and GRPO. ``shard_batch`` is a no-copy
        ``device_put`` for already-placed device arrays, so feeding the
        generation's outputs straight through costs nothing. One dispatch,
        or one a length group where the chunk's rows need different rungs of
        the learner's query ladder (:func:`score_groups`, at the end of this file)."""
        B, P, N = shape
        groups = None if prompt_ids is None else self._score_groups(prompt_mask)
        rows = shard_batch({"response_tokens": response_tokens, "response_mask": response_mask}, self.mesh)
        outs = []
        for take, width in groups or [(None, P)]:
            key = (B if take is None else len(take), width, N)
            whole = {"sequences": sequences, "prompt_mask": prompt_mask}
            batch = shard_batch(whole if take is None else group_prompts(prompt_ids, prompt_mask, take, width), self.mesh)
            score_fn = self._get_score_fn(key)
            score_out = score_fn(
                self.state.params if params is None else params, self.ref_params,
                batch["sequences"], batch["prompt_mask"], rows["response_tokens"], rows["response_mask"],
                take=take,
            )
            self.obs.recompile.observe("score", score_fn, planned=key)  # a planned shape's first compile passes
            # start the device→host copies of the scoring outputs without
            # blocking: by the time the host stage asks for these arrays they
            # have usually landed
            for leaf in jax.tree_util.tree_leaves(score_out):
                if hasattr(leaf, "copy_to_host_async"):
                    leaf.copy_to_host_async()
            outs.append(score_out)
        return outs[0] if groups is None else {"groups": outs, "takes": [take for take, _ in groups]}

    def _fan_out(self, prompt_ids, prompt_mask) -> Tuple[np.ndarray, np.ndarray]:
        """Every prompt row repeated ``_rollout_fanout`` times, group-
        contiguous (rows ``g*F .. g*F+F-1`` are group ``g``)."""
        fanout = self._rollout_fanout
        if fanout > 1:
            prompt_ids = np.repeat(prompt_ids, fanout, axis=0)
            prompt_mask = np.repeat(prompt_mask, fanout, axis=0)
        return prompt_ids, prompt_mask

    def _next_prompt_chunk(self) -> Tuple[np.ndarray, np.ndarray]:
        """The next prompt batch as host ``(ids, mask)``, fanned out."""
        with self.obs.span("collect/prompts"):
            batch = next(self.prompt_iterator)
            return self._fan_out(
                np.asarray(batch["input_ids"], np.int32),
                np.asarray(batch["attention_mask"], np.int32),
            )

    def _chunk_device(
        self,
        prompt_ids: np.ndarray,
        prompt_mask: np.ndarray,
        stats: Dict[str, float],
        params=None,
        rng=None,
    ) -> Dict[str, Any]:
        """Device side of one prompt chunk, prompt batch supplied by the
        caller — shared verbatim between the serial reference path (trainer
        state params/RNG) and the async actor path (channel-published
        params, dispatched per-chunk RNG)."""
        gen_time = perf_counter()
        # generate() opens its own fenced "generate" span, nested under the
        # caller's "rollout" span in the Chrome/Perfetto export
        gen_out = self.generate(prompt_ids, prompt_mask, params=params, rng=rng)
        # sums over the collection's chunks (one `stats` a collection)
        _add_times(
            stats,
            {
                "time/exp_generate": perf_counter() - gen_time,
                "time/generate": self.last_generate_span.duration,
                "time/generate_dispatch": self.last_generate_span.dispatch,
                "time/generate_wait": self.last_generate_span.wait,
            },
        )
        spec_stats = dict(self.last_spec_stats)
        stats.update(self.last_cache_stats)

        # dispatch the scoring forward immediately on the generation's
        # device arrays — it needs nothing from the host, so it runs while
        # the host stage decodes strings and calls reward_fn
        B, P = prompt_ids.shape
        N = int(gen_out.response_tokens.shape[1])
        score_out = self._dispatch_score(
            (B, P, N),
            gen_out.sequences,
            prompt_mask,
            gen_out.response_tokens,
            gen_out.response_mask,
            params=params, prompt_ids=prompt_ids,
        )
        return {
            "prompt_ids": prompt_ids,
            "prompt_mask": prompt_mask,
            "gen_out": gen_out,
            "score_out": score_out,
            "kv_extents": self.last_kv_extents,
            "kv_layers": self.last_kv_layers,
            "spec_stats": spec_stats,
        }

    def _rollout_chunk_host(self, dev: Dict[str, Any]) -> Dict[str, Any]:
        """Host side of one chunk (pipeline worker when depth > 0): fetch the
        generation outputs, decode strings, run ``reward_fn``, land the
        scoring outputs. The "score" span covers execution → host landing of
        the scoring forward: it deliberately stays open across the
        interleaved decode/reward work, so the recorded time includes the
        overlap window rather than serializing it."""
        host_t0 = perf_counter()
        # named `stats` so scripts/check_metric_names.py lints these keys too
        stats: Dict[str, float] = {}
        with ExitStack() as score_ctx:
            # ExitStack (not a plain `with`) mirrors the historical shape:
            # the span must close even if decode/reward raises mid-overlap
            score_sp = score_ctx.enter_context(self.obs.span("score"))
            # to_host already lands numpy arrays — no further conversion
            host_gen = to_host(
                {
                    "response_tokens": dev["gen_out"].response_tokens,
                    "response_mask": dev["gen_out"].response_mask,
                }
            )
            response_tokens = host_gen["response_tokens"]
            response_mask = host_gen["response_mask"]

            samples, prompts, outputs = self.decode(
                dev["prompt_ids"], response_tokens, append_eos_token=True
            )
            with self.obs.span("reward") as reward_sp:
                scores = np.asarray(
                    self.reward_fn(samples=samples, prompts=prompts, outputs=outputs),
                    dtype=np.float32,
                )
            stats["time/reward"] = reward_sp.duration
            stats["time/exp_score"] = reward_sp.duration
            wait_t0 = perf_counter()
            host = scores_in_chunk_order(to_host(dev["score_out"]))  # usually landed already (async copy)
            score_wait = perf_counter() - wait_t0
        stats["time/score"] = score_sp.duration
        return {
            "prompt_ids": dev["prompt_ids"],
            "prompt_mask": dev["prompt_mask"],
            "response_tokens": response_tokens,
            "response_mask": response_mask,
            "scores": scores,
            "host": host,
            "kv_extents": dev.get("kv_extents"),
            "kv_layers": dev.get("kv_layers"),
            "spec_stats": dev.get("spec_stats"),
            "stats": stats,
            "host_s": perf_counter() - host_t0,
            # what this stage spent inside reward_fn and waiting for the
            # scoring outputs: not host work of the collection itself
            "blocked_s": reward_sp.duration + score_wait,
        }

    def _host_stage_job(self, dev: Dict[str, Any]):
        """The host stage of ``dev`` as a job for the pipeline worker."""

        def work() -> Dict[str, Any]:
            # fenced: the span closes only once the scoring outputs are
            # device-complete, so its duration is host-true
            with self.obs.span("rollout/overlap") as sp:
                sp.fence(dev["score_out"])
                return self._rollout_chunk_host(dev)

        return work

    def _rollout_chunk_finalize(
        self,
        chunk: Dict[str, Any],
        elements: list,
        stats: Dict[str, float],
        acc: Dict[str, float],
    ) -> None:
        """Ordered tail of one chunk — the sequential dependencies. Runs on
        the main thread in submission order in EVERY mode, so reward scaling
        (running moments) and the store contents are bit-identical between
        depth 0 and depth ≥ 1. What turns scores into stored elements is the
        algorithm's (:meth:`_chunk_element_fn`); the rest is shared."""
        with self.obs.span("collect/finalize"):
            _add_times(stats, chunk["stats"])
            acc["host_s"] += chunk["host_s"]
            response_mask, response_tokens = chunk["response_mask"], chunk["response_tokens"]
            host = chunk["host"]
            self._note_score_slots(chunk, acc)

            # Non-finite scores (a flaky reward endpoint, an overflowed RM)
            # are zeroed BEFORE the running moments fold them in —
            # RunningMoments state is cumulative, so one NaN would poison
            # every subsequently scaled reward.
            scores = np.asarray(chunk["scores"], np.float32)
            nonfinite = ~np.isfinite(scores)
            if nonfinite.any():
                stats["health/nonfinite_scores"] = stats.get(
                    "health/nonfinite_scores", 0.0
                ) + float(nonfinite.sum())
                scores = np.where(nonfinite, 0.0, scores)
            element_of = self._chunk_element_fn(chunk, scores, stats, acc)
            acc["gen_tokens"] += int(response_mask.sum())
            acc["chunks"] += 1

            # rollout-side dynamics sketches (observability/dynamics.py): the
            # per-token KL vs the frozen reference only exists host-side here
            # (the train step sees new-vs-old only), and all four collection
            # paths (serial / pipelined / continuous / async) funnel through
            # this finalize — one uniform feed point for the health canary
            fmask = np.asarray(response_mask, np.float32)
            ref_lr = (
                np.asarray(host["logprobs"]) - np.asarray(host["ref_logprobs"])
            ) * fmask
            ref_k3 = (np.exp(ref_lr) - 1.0) - ref_lr
            lo, hi = SKETCH_RANGES["ref_kl"]
            acc["ref_kl_hist"] = acc.get("ref_kl_hist", 0.0) + sketch_np(
                ref_k3, fmask, lo=lo, hi=hi
            )
            # generation-length + repeated-adjacent-token canary (host twin of
            # the engine-harvest counters; engine's exact numbers win via
            # setdefault in make_experience on the continuous path)
            toks = np.asarray(response_tokens)
            pair_mask = fmask[:, 1:] * fmask[:, :-1]
            acc["rep_pairs"] = acc.get("rep_pairs", 0.0) + float(
                ((toks[:, 1:] == toks[:, :-1]) * pair_mask).sum()
            )
            acc["rep_total"] = acc.get("rep_total", 0.0) + float(pair_mask.sum())
            acc.setdefault("gen_lens", []).extend(
                fmask.sum(axis=1).astype(np.int64).tolist()
            )

            # slot accounting (docs/PERFORMANCE.md): a chunk's decode ran
            # max(n_i) steps over B slots (per-sample eos early-exit ends the
            # while_loop at the longest row) — rows past their own eos burned
            # padded slot-steps. The continuous-batching path replaces these
            # numbers with the engine's exact counters.
            n_per_row = response_mask.sum(axis=1)
            decode_steps = int(n_per_row.max()) if n_per_row.size else 0
            acc["decode_steps"] += decode_steps
            spec = chunk.get("spec_stats")
            if spec:
                # under a drafter a pass of the loop is a ROUND, which commits one to
                # gamma + 1 tokens a row: row-rounds take the place of slot-steps (the
                # same numbers where a round is a token), summed over the chunks
                for key in ("rollout/spec_rounds", "rollout/draft_proposed", "rollout/draft_accepted", "rollout/spec_live_row_rounds"):
                    acc[key] = acc.get(key, 0) + spec[key]
                acc["slot_steps"] += int(response_mask.shape[0]) * spec["rollout/spec_rounds"]
                acc["live_slot_steps"] += spec["rollout/spec_live_row_rounds"]
                acc["committed_live"] = acc.get("committed_live", 0.0) + spec["rollout/tokens_per_round"] * spec["rollout/spec_live_row_rounds"]
            else:
                acc["slot_steps"] += int(response_mask.shape[0]) * decode_steps
                acc["live_slot_steps"] += int(n_per_row.sum())
            # cache slots a row's attention read over those steps, a layer:
            # the dense sampler's steps stop at a static extent of the cache
            # (ops/sampling.py::kv_extents), and a window layer's cache is a
            # ring of at most its window; every other sampler reads it whole.
            # Both over steps x S x layers, so a uniform stack reads what one
            # of its layers does
            P, N = chunk["prompt_ids"].shape[1], response_mask.shape[1]
            extents = chunk.get("kv_extents") or (P + N,)
            for slots, windowed in chunk.get("kv_layers") or ((P + N, False),):
                if spec:
                    # a round's verify reads a layer's cache whole, at each row's own depth
                    # (no static extent holds for all rows): its ring, or every slot of the row
                    passes = spec["rollout/spec_rounds"]
                    read = passes * slots
                else:
                    passes = decode_steps
                    read = kv_slots_read(layer_extents(extents, slots), P, decode_steps, getattr(self.tcfg, "index_topk", 0))
                acc["kv_slots_read"] += read
                acc["kv_slots"] += passes * (P + N)
                if windowed:
                    acc["kv_window_slots_read"] += read
                    acc["kv_window_slots"] += passes * (P + N)

            prompt_ids, prompt_mask = chunk["prompt_ids"], chunk["prompt_mask"]
            # async chunks ship the sampler's exact behavior logprobs; they ride
            # into elements only when the IW correction will consume them — the
            # default-off path keeps the store's field set (and bytes) identical
            # to the serial reference
            behavior = chunk.get("behavior_logprobs")
            if self.config.method.iw_correction == "off":
                behavior = None
            for i in range(prompt_ids.shape[0]):
                n_i = int(response_mask[i].sum())
                if n_i == 0:
                    continue
                elements.append(
                    element_of(
                        i,
                        n_i,
                        query_tensor=prompt_ids[i][prompt_mask[i] > 0],
                        response_tensor=response_tokens[i, :n_i],
                        behavior_logprobs=(
                            np.asarray(behavior[i, :n_i], np.float32)
                            if behavior is not None
                            else None
                        ),
                    )
                )

    def _chunk_element_fn(
        self,
        chunk: Dict[str, Any],
        scores: np.ndarray,  # [B] float32, finite
        stats: Dict[str, float],
        acc: Dict[str, float],
    ):
        """Hook 2 of 3: fold one chunk's scores into the algorithm's state
        (running moments, ``acc["kl_sum"]`` / ``acc["kl_batches"]``) and
        return ``element(i, n_i, **common)`` building row ``i``'s stored
        element from its first ``n_i`` response positions. PPO: reward
        scaling (reference :350-366), clipping, KL-penalty rewards."""
        host, response_mask = chunk["host"], chunk["response_mask"]
        method: PPOConfig = self.config.method
        scores_mean, scores_std = self.running_moments.update(scores)
        stats["exp_scores/mean"] = float(scores_mean)
        stats["exp_scores/std"] = float(scores_std)
        stats["exp_scores/running_mean"] = float(self.running_moments.mean)
        stats["exp_scores/running_std"] = float(self.running_moments.std)
        if method.scale_reward == "running":
            scores /= max(self.running_moments.std, 1e-8)
        elif method.scale_reward == "ref":
            scores /= max(self.ref_std or 1.0, 1e-8)
        clip = method.cliprange_reward
        if clip:
            scores = np.clip(scores, -clip, clip)

        # KL-penalty reward assembly on host (numpy twin of the device
        # math; [B, N] arrays — microseconds)
        rewards, (mean_kl, _) = kl_penalty_rewards_np(
            host["logprobs"], host["ref_logprobs"], response_mask,
            scores, self.kl_ctl.value,
        )
        # a non-finite chunk KL (one overflowed logprob) must reach neither
        # the adaptive controller's accumulator nor the tracker stream —
        # max(nan, 0.0) is nan, so the old sqrt guard passed NaN through
        if np.isfinite(mean_kl):
            acc["kl_sum"] += mean_kl
            acc["kl_batches"] += 1
            stats["policy/sqrt_kl"] = float(np.sqrt(max(mean_kl, 0.0)))
        else:
            stats["health/nonfinite_kl_chunks"] = stats.get(
                "health/nonfinite_kl_chunks", 0.0
            ) + 1.0
            stats["policy/sqrt_kl"] = 0.0

        def element(i: int, n_i: int, **common) -> PPORLElement:
            # host[...] landed via to_host: already numpy, slices need no
            # re-asarray
            return PPORLElement(
                logprobs=host["logprobs"][i, :n_i],
                values=host["values"][i, :n_i],
                rewards=rewards[i, :n_i],
                **common,
            )

        return element

    def _collect_serial(
        self, num_rollouts: int, elements: list, stats: Dict[str, float],
        acc: Dict[str, float],
    ) -> None:
        """Depth-0 reference implementation: each chunk runs device → host →
        finalize strictly in sequence. Kept verbatim as the equivalence
        baseline the pipelined path is tested against."""
        while len(elements) < num_rollouts:
            # the span feeds the trace; the time/rollout *stat* is computed
            # uniformly for both modes in make_experience (wall ÷ chunks)
            with self.obs.span("rollout"):
                dev = self._chunk_device(*self._next_prompt_chunk(), stats)
                chunk = self._rollout_chunk_host(dev)
            acc["blocked_s"] += chunk["blocked_s"]
            self._rollout_chunk_finalize(chunk, elements, stats, acc)
        stats["throughput/rollout_overlap_frac"] = 0.0

    def _collect_pipelined(
        self, num_rollouts: int, depth: int, elements: list,
        stats: Dict[str, float], acc: Dict[str, float],
    ) -> None:
        """Software-pipelined collection: the main thread keeps the device
        busy (chunk k+1's generation dispatches as soon as chunk k's lands)
        while up to ``depth`` chunks of host work drain on the pipeline
        worker. Finalization happens on this thread in submission order —
        see the stage map above for why the result is bit-identical."""
        from trlx_tpu.pipeline.rollout_pipeline import RolloutPipeline

        # upper-bound row count of each in-flight chunk, submission order
        rows_in_flight: deque = deque()

        def finalize(chunk: Dict[str, Any]) -> None:
            rows_in_flight.popleft()
            self._rollout_chunk_finalize(chunk, elements, stats, acc)

        t0 = perf_counter()
        with RolloutPipeline(
            depth=depth, finalize=finalize, name="rollout", tracer=self.obs.tracer
        ) as pipe:
            while True:
                # submit while even full chunks cannot cover the target; when
                # the in-flight upper bound says "maybe enough", drain and
                # re-check with exact counts (rows with empty responses are
                # dropped at finalize). The set of chunks processed is
                # therefore exactly the serial loop's.
                if len(elements) + sum(rows_in_flight) >= num_rollouts:
                    pipe.drain()
                    if len(elements) >= num_rollouts:
                        break
                    continue
                # the "rollout" span covers the device side only here; the
                # host side shows up as "rollout/overlap" on the worker tid
                with self.obs.span("rollout", pipelined=True) as rollout_sp:
                    dev = self._chunk_device(*self._next_prompt_chunk(), stats)
                _add_times(stats, {"time/rollout_device": rollout_sp.duration})
                rows_in_flight.append(int(dev["prompt_ids"].shape[0]))
                pipe.submit(self._host_stage_job(dev))
            pipe_stats = pipe.stats
        # reward and the wait for the scoring outputs ran on the worker: the
        # main thread was blocked on them only while it waited on the pipe
        acc["blocked_s"] += pipe_stats.wait_s
        stats["throughput/rollout_overlap_frac"] = pipe_stats.overlap_frac(
            perf_counter() - t0
        )

    # ------------------------------------------------------------------
    # continuous batching (train.continuous_batching)
    # ------------------------------------------------------------------

    def _cb_group_device(self, group: list, params=None) -> Dict[str, Any]:
        """Device side of one harvested group: assemble the score batch from
        individually completed sequences and dispatch the scoring forward
        with async device→host copies — the same ``dev`` contract as
        :meth:`_chunk_device`, so the host/finalize stages are
        shared verbatim with the chunked paths."""
        prompt_ids = np.stack([c.prompt_ids for c in group]).astype(np.int32)
        prompt_mask = np.stack([c.prompt_mask for c in group]).astype(np.int32)
        response_tokens = np.stack([c.tokens for c in group]).astype(np.int32)
        response_mask = np.stack([c.mask for c in group]).astype(np.int32)
        gen_out = GenerationOutput(
            sequences=np.concatenate([prompt_ids, response_tokens], axis=1),
            response_tokens=response_tokens,
            response_mask=response_mask,
            response_logprobs=np.stack([c.logprobs for c in group]),
            response_values=np.stack([c.values for c in group]),
            prompt_mask=prompt_mask,
        )
        B, P = prompt_ids.shape
        N = int(response_tokens.shape[1])
        score_out = self._dispatch_score(
            (B, P, N),
            np.asarray(gen_out.sequences),
            prompt_mask,
            response_tokens,
            response_mask,
            params=params, prompt_ids=prompt_ids,
        )
        return {
            "prompt_ids": prompt_ids,
            "prompt_mask": prompt_mask,
            "gen_out": gen_out,
            "score_out": score_out,
        }

    def _cb_make_engine(
        self, gen_config, extra_kwargs, rows: int, chunk_width: int,
        tag: Any = None, params: Any = None, version: Any = None,
    ):
        """Build the rollout engine for this trainer — the single home of
        the engine-width invariant (PPO and GRPO must agree): the trainer-
        level prompt budget ``seq_length − max_new_tokens``, bumped to the
        first chunk's collation width if a loader pads wider. Prompt loaders
        pad to the longest row per batch, and the engine's one compiled
        shape must fit every chunk; narrower chunks left-pad
        (attention-masked, so harvested sequences stay bit-identical to
        plain generate at THIS width).

        The KV backend (dense per-slot vs paged block pool) and the prefix
        cache come from the ``engine:`` config section
        (docs/PERFORMANCE.md); outputs are bit-identical across backends,
        so the choice is purely a memory/throughput knob. Engines are
        cached per shape bucket and reused across collections —
        ``begin_collection`` resets the per-collection stats, and flushes
        the prefix cache exactly when the params tree changed (cached KV
        is only valid under the params that computed it)."""
        from trlx_tpu.engine.core import ContinuousEngine

        seg = max(
            1, int(getattr(self.config.train, "continuous_batching_segment", 8) or 8)
        )
        engine_p = max(
            int(self.config.train.seq_length) - gen_config.max_new_tokens,
            chunk_width,
        )
        key = ("cb_engine", gen_config, extra_kwargs, rows, engine_p, seg, tag)
        engine = self._generate_fns.get(key)
        if engine is None:
            fns = self._get_slot_refill_fns(
                gen_config, extra_kwargs, rows, engine_p, seg
            )
            engine = ContinuousEngine(
                fns,
                self._engine_params(params),
                self.tokenizer.pad_token_id,
                span=self.obs.span,
                # per-request lifecycle spans (engine/queue_wait → prefill →
                # decode on per-slot tracks; docs/OBSERVABILITY.md)
                tracer=self.obs.tracer,
                prefix_cache=self._prefix_cache_enabled(),
                prefix_capacity_blocks=int(self.config.engine.prefix_cache_blocks),
                # chunked-prefill scheduling: long prompts admit instantly
                # and prefill one span per step between decode segments
                prefill_chunk=int(self.config.engine.prefill_chunk),
            )
            self._generate_fns[key] = engine
        engine.begin_collection(self._engine_params(params), version=version)
        return engine

    def _cb_chunk_keys(self, rows: int) -> np.ndarray:
        """Per-row RNG chain starts for one prompt chunk: one rng split per
        chunk, then ``fold_in(row)`` — the exact chain plain generate
        derives in per_row_rng mode, so every prompt's sample stream is
        reproducible by the serial sampler."""
        from trlx_tpu.ops.sampling import per_row_keys

        self._rollout_rng, call_rng = jax.random.split(self._rollout_rng)
        return np.asarray(per_row_keys(call_rng, rows))

    def _collect_continuous(
        self, num_rollouts: int, depth: int, elements: list,
        stats: Dict[str, float], acc: Dict[str, float],
    ) -> None:
        """Continuous-batching collection: slot-refill segment decode keeps
        the device batch full while finished sequences stream — harvested
        individually at segment boundaries, grouped into score batches in
        completion order — through the scoring forward and (when
        ``rollout_pipeline_depth`` > 0) the PR-2 host pipeline. Per-sequence
        sampling is bit-identical to plain ``generate`` under per-row RNG;
        the chunk barrier of the serial path is gone, so the store matches
        the serial-with-per-row-RNG store up to sequence order
        (tests/test_continuous_batching.py).

        The harvest is group-aware: rows carry ``(group, member)`` metas, a
        group is ready when its ``_rollout_fanout`` members have completed,
        and ready groups flush in completion order with members in member
        order, so every score batch is group-contiguous. With fan-out 1
        each completion is its own ready group."""
        from trlx_tpu.pipeline.rollout_pipeline import RolloutPipeline

        if num_rollouts <= 0:
            stats["throughput/rollout_overlap_frac"] = 0.0
            return
        gen_config, extra_kwargs = self._resolve_gen_config(eval_mode=False)
        fanout = self._rollout_fanout
        state = {"engine": None, "supplied": 0, "finalized_rows": 0, "next_group": 0}
        partial: Dict[int, list] = {}  # group id → completed members
        ready: deque = deque()  # fully-completed groups, completion order

        def fetch_chunk() -> None:
            ids, mask = self._next_prompt_chunk()
            rows = ids.shape[0]
            keys = self._cb_chunk_keys(rows)
            metas = [(state["next_group"] + r // fanout, r % fanout) for r in range(rows)]
            state["next_group"] += rows // fanout
            if state["engine"] is None:
                state["engine"] = self._cb_make_engine(
                    gen_config, extra_kwargs, rows, ids.shape[1]
                )
            state["engine"].enqueue_prompts(ids, mask, keys, metas=metas)
            state["supplied"] += rows

        def finalize(chunk: Dict[str, Any]) -> None:
            state["finalized_rows"] += int(chunk["prompt_ids"].shape[0])
            self._rollout_chunk_finalize(chunk, elements, stats, acc)

        t0 = perf_counter()
        with ExitStack() as ctx:
            pipe = None
            if depth > 0:
                pipe = ctx.enter_context(
                    RolloutPipeline(
                        depth=depth, finalize=finalize, name="rollout",
                        tracer=self.obs.tracer,
                    )
                )

            def flush(n_groups: int) -> None:
                dev = self._cb_group_device(
                    [
                        member
                        for _ in range(n_groups)
                        for member in sorted(ready.popleft(), key=lambda c: c.meta[1])
                    ]
                )
                if pipe is None:
                    finalize(self._rollout_chunk_host(dev))
                else:
                    pipe.submit(self._host_stage_job(dev))

            while True:
                # supply so the queue can (expected-case) cover the target;
                # every supplied row yields an element unless its response
                # is empty, in which case the drain below tops up
                while (
                    len(elements) + state["supplied"] - state["finalized_rows"]
                    < num_rollouts
                ):
                    fetch_chunk()
                engine = state["engine"]
                groups_per_batch = max(engine.B // fanout, 1)
                if not engine.busy:
                    if ready:  # the (possibly partial) tail
                        flush(len(ready))
                    if pipe is not None:
                        pipe.drain()
                    if len(elements) >= num_rollouts:
                        break
                    continue
                for c in engine.step():
                    members = partial.setdefault(c.meta[0], [])
                    members.append(c)
                    if len(members) == fanout:
                        ready.append(partial.pop(c.meta[0]))
                while len(ready) >= groups_per_batch:
                    flush(groups_per_batch)
            if pipe is not None:
                stats["throughput/rollout_overlap_frac"] = pipe.stats.overlap_frac(
                    perf_counter() - t0
                )
            else:
                stats["throughput/rollout_overlap_frac"] = 0.0

        engine = state["engine"]
        if engine is not None:
            # exact on-device counters replace the mask-derived estimates
            engine_metrics = engine.stats.metrics()
            stats.update(engine_metrics)
            stats["time/exp_generate"] = engine.stats.decode_s + engine.stats.refill_s
            stats["time/generate"] = engine.stats.decode_s
            # EngineStats snapshot into the crash flight recorder: a run
            # dying mid-collection keeps its last engine picture
            self.obs.flightrec.record("engine_stats", engine_metrics)

    # ------------------------------------------------------------------
    # disaggregated async collection (async_rl.enabled; docs/ASYNC_RL.md)
    # ------------------------------------------------------------------
    #
    # The actor/learner split: N actors (threads here, or run_actor
    # processes) produce experience chunks continuously — gated by the
    # weight channel's staleness bound — while the learner drains chunks in
    # index order and trains. The learner publishes params after every
    # update (in-flight weight sync), so collection k+1 is generated under
    # params at most max_staleness updates behind its consumption.

    def _async_chunks_per_collection(self) -> int:
        from trlx_tpu.async_rl.actor import chunks_per_collection

        return chunks_per_collection(self.config)

    def _async_queue_capacity(self) -> int:
        cap = int(self.config.async_rl.queue_capacity)
        return cap if cap > 0 else 2 * self._async_chunks_per_collection()

    def _async_updates_per_phase(self) -> int:
        """Optimizer updates between two collections: one learn-loop epoch
        (the gate target the learner announces at drain end)."""
        method = self.config.method
        batches = max(1, int(method.num_rollouts) // int(self.config.train.batch_size))
        return int(method.ppo_epochs) * batches

    def _ensure_async_collector(self):
        if self._async is not None:
            return self._async
        import os as _os

        from trlx_tpu.async_rl.channel import FileWeightChannel, WeightChannel
        from trlx_tpu.async_rl.queue import ExperienceQueue, FileExperienceQueue
        from trlx_tpu.async_rl.runtime import AsyncCollector

        acfg = self.config.async_rl
        capacity = self._async_queue_capacity()
        coordinator = None
        member_factory = None
        if acfg.transport not in ("file", "collective"):
            raise ValueError(
                f"unknown async_rl.transport '{acfg.transport}' "
                "(file | collective)"
            )
        if acfg.transport == "collective":
            # the fleet fabric (async_rl/transport.py): param-dissemination
            # tree + in-fabric chunk commits + elastic membership. The file
            # transports below remain the degraded/fallback mode.
            if acfg.queue_policy == "drop_oldest":
                raise ValueError(
                    "async_rl.transport: collective back-pressures through "
                    "the fleet production window; queue_policy: drop_oldest "
                    "is a file-transport knob"
                )
            from trlx_tpu.async_rl.transport import (
                CollectiveExperienceQueue,
                CollectiveWeightChannel,
                FleetCoordinator,
                make_member_factory,
                write_endpoint,
            )

            coordinator = FleetCoordinator(
                fanout=acfg.fanout,
                bind_host=acfg.bind_host,
                capacity=capacity,
                plan=self.resilience.plan,
                metrics=self.obs.metrics,
                sync_every=acfg.sync_every,
                actor_timeout_s=acfg.actor_timeout_s,
            )
            queue = CollectiveExperienceQueue(coordinator)
            channel = CollectiveWeightChannel(coordinator)
            if acfg.mode == "process":
                if not acfg.root_dir:
                    raise ValueError(
                        "async_rl.mode: process requires async_rl.root_dir "
                        "(endpoint discovery for the run_actor processes)"
                    )
                write_endpoint(
                    acfg.root_dir, coordinator.address, coordinator.authkey
                )
                spawn = False  # actors are external run_actor processes
            elif acfg.mode == "thread":
                # each actor thread joins the fleet as its own member over
                # loopback — the same wire protocol as a pod's processes
                member_factory = make_member_factory(
                    coordinator, lambda: self.state.params
                )
                spawn = True
            else:
                raise ValueError(
                    f"unknown async_rl.mode '{acfg.mode}' (thread | process)"
                )
        elif acfg.mode == "process":
            if not acfg.root_dir:
                raise ValueError(
                    "async_rl.mode: process requires async_rl.root_dir (a "
                    "directory shared with the run_actor processes)"
                )
            queue = FileExperienceQueue(
                _os.path.join(acfg.root_dir, "spool"),
                capacity=capacity,
                poll_interval_s=acfg.poll_interval_s,
                metrics=self.obs.metrics,
            )
            channel = FileWeightChannel(
                _os.path.join(acfg.root_dir, "weights"),
                plan=self.resilience.plan,
                metrics=self.obs.metrics,
                sync_every=acfg.sync_every,
                poll_interval_s=acfg.poll_interval_s,
                fetch_timeout_s=acfg.fetch_timeout_s,
            )
            spawn = False  # actors are external run_actor processes
        elif acfg.mode == "thread":
            queue = ExperienceQueue(
                capacity,
                policy=acfg.queue_policy,
                metrics=self.obs.metrics,
                # late-bound through self._async: evicted chunks regenerate
                on_drop=(
                    self._async_on_drop
                    if acfg.queue_policy == "drop_oldest" else None
                ),
            )
            channel = WeightChannel(
                plan=self.resilience.plan,
                metrics=self.obs.metrics,
                sync_every=acfg.sync_every,
            )
            spawn = True
        else:
            raise ValueError(
                f"unknown async_rl.mode '{acfg.mode}' (thread | process)"
            )
        self._async = AsyncCollector(
            trainer=self,
            queue=queue,
            channel=channel,
            num_actors=acfg.num_actors,
            max_staleness=acfg.max_staleness,
            updates_per_phase=self._async_updates_per_phase(),
            chunks_per_collection=self._async_chunks_per_collection(),
            spawn_actors=spawn,
            chunk_timeout_s=acfg.actor_timeout_s,
            max_actor_restarts=acfg.max_actor_restarts,
            metrics=self.obs.metrics,
            tracer=self.obs.tracer,
            span=self.obs.span,
            member_factory=member_factory,
            transport=coordinator,
        )
        self._async.version = self._async_version
        return self._async

    def _async_on_drop(self, chunk) -> None:
        """drop_oldest eviction callback: hand the evicted chunk back to the
        collector for regeneration under fresher params."""
        if self._async is not None:
            self._async.requeue_dropped(chunk)

    def _async_produce_chunk(self, spec, params, version, channel) -> Dict[str, Any]:
        """One actor chunk, device + host halves, under the actor's adopted
        ``params`` (a channel copy — NEVER ``state.params``, whose buffers
        the donated train step invalidates). Serial generation by default;
        with ``train.continuous_batching`` the chunk decodes on the
        slot-refill engine with PipelineRL-style in-flight weight swaps at
        segment boundaries. The payload always carries the sampler's exact
        behavior logprobs — under in-flight swaps they are the only honest
        record of the (mixed-version) behavior policy."""
        stats: Dict[str, float] = {}
        if bool(getattr(self.config.train, "continuous_batching", False)):
            if self._rollout_fanout > 1:
                raise NotImplementedError(
                    "async_rl + train.continuous_batching is implemented for a "
                    "rollout fan-out of 1 (PPO) only: the group-aware harvest "
                    "keeps the single-program CB loop. Drop one of the two."
                )
            dev = self._async_produce_cb(spec, params, version, channel, stats)
        else:
            dev = self._chunk_device(
                *self._fan_out(spec.prompt_ids, spec.prompt_mask), stats,
                params=params, rng=spec.rng,
            )
        chunk = self._rollout_chunk_host(dev)
        chunk["stats"].update(stats)
        chunk["behavior_logprobs"] = np.asarray(
            dev["gen_out"].response_logprobs, np.float32
        )
        return chunk

    def _async_produce_cb(
        self, spec, params, version, channel, stats: Dict[str, float]
    ) -> Dict[str, Any]:
        """Continuous-batching actor chunk: slot-refill segment decode over
        the chunk's prompts with per-row RNG, adopting newly published
        params at every segment boundary (``ContinuousEngine.swap_params``'s
        memoized version counter makes the per-segment check one int
        compare; a real change flushes the prefix cache so stale shared KV
        is never reused). Live rows keep decoding across a swap — their
        recorded logprobs remain the exact behavior distribution."""
        import threading as _threading

        from trlx_tpu.ops.sampling import per_row_keys

        gen_config, extra_kwargs = self._resolve_gen_config(eval_mode=False)
        ids, mask = spec.prompt_ids, spec.prompt_mask
        engine = self._cb_make_engine(
            gen_config, extra_kwargs, ids.shape[0], ids.shape[1],
            tag=("async", _threading.get_ident()),
            params=params, version=version,
        )
        keys = np.asarray(per_row_keys(spec.rng, ids.shape[0]))
        engine.enqueue_prompts(ids, mask, keys)
        completed = []
        while engine.busy:
            completed.extend(engine.step())
            if channel is not None and engine.busy:
                fresh, fresh_version = channel.fetch(template=self.state.params)
                # spec engines swap the (target, draft) tuple atomically
                engine.swap_params(self._engine_params(fresh), fresh_version)
        completed.sort(key=lambda c: c.index)
        stats["time/exp_generate"] = engine.stats.decode_s + engine.stats.refill_s
        stats["time/generate"] = engine.stats.decode_s
        gen_params = engine.params
        if int(self.config.engine.speculative):
            gen_params = gen_params[0]  # scoring runs under the target
        return self._cb_group_device(completed, params=gen_params)

    def _collect_async(
        self, num_rollouts: int, elements: list, stats: Dict[str, float],
        acc: Dict[str, float],
    ) -> None:
        """Learner-side drain: consume actor chunks in strict index order
        (running moments fold exactly as the serial path's) and finalize on
        this thread. ``begin_collection`` force-publishes the params this
        collection is consumed under; ``end_collection`` announces the
        upcoming phase's end version — the staleness gate for the chunks
        feeding the NEXT collection."""
        collector = self._ensure_async_collector()
        collector.begin_collection()
        while len(elements) < num_rollouts:
            chunk = collector.next_chunk()
            self._rollout_chunk_finalize(chunk.payload, elements, stats, acc)
        collector.end_collection()
        stats.update(collector.collection_stats())

    def train_step(self, batch, step=None):
        stats = super().train_step(batch, step=step)
        if self._async is not None:
            # the learner's update clock IS the weight-channel version:
            # publish after every optimizer update (in-flight sync; thinned
            # by async_rl.sync_every inside the channel)
            self._async_version += 1
            self._async.on_update(self.state.params, self._async_version)
        return stats

    def _shutdown_collectors(self) -> None:
        # actors first (they draw from the prompt iterator), then the
        # base closes the iterator chain and joins the prefetch worker
        if self._async is not None:
            try:
                self._async.close()
            except Exception:  # pragma: no cover - defensive
                pass
        super()._shutdown_collectors()

    def _consume_skip_initial_experience(self) -> bool:
        """True exactly once after an emergency-payload restore: the store
        already holds the rollouts this collection would replace."""
        if getattr(self, "_skip_initial_experience", False):
            self._skip_initial_experience = False
            logger.info(
                "emergency resume: rollout store restored from the checkpoint; "
                "skipping the initial collection"
            )
            return True
        return False

    def make_experience(self, num_rollouts: int = 1024, iter_count: int = 0) -> None:
        """Collect ``num_rollouts`` experiences into the store (reference
        ``accelerate_ppo_trainer.py:251-489``), overlapping device generation
        with host reward scoring when ``train.rollout_pipeline_depth`` > 0."""
        if self._consume_skip_initial_experience():
            return
        logger.info("Collecting rollouts")
        if self.prompt_iterator is None:
            raise RuntimeError("add_prompt_pipeline must be called before make_experience")

        depth = int(getattr(self.config.train, "rollout_pipeline_depth", 0) or 0)
        continuous = bool(getattr(self.config.train, "continuous_batching", False))
        stats: Dict[str, float] = {}
        elements: list = []
        acc: Dict[str, float] = {
            "kl_sum": 0.0, "kl_batches": 0, "host_s": 0.0,
            "gen_tokens": 0, "chunks": 0,
            "slot_steps": 0, "live_slot_steps": 0,
            "decode_steps": 0, "blocked_s": 0.0,
            "kv_slots_read": 0, "kv_slots": 0,
            "kv_window_slots_read": 0, "kv_window_slots": 0,
        }
        self._close_cycle()
        if self.obs.tracer.cycle == 1:  # the second collection: set-up is over
            self.obs.freeze_setup(self.programs.account())
        self.obs.tracer.next_cycle()
        with self.obs.span("collect/experience"):
            mark = tracing.mark()
            exp_time = perf_counter()

            if bool(self.config.async_rl.enabled):
                # the actor/learner split (docs/ASYNC_RL.md): actors generate —
                # continuously, across collections — and this thread only drains
                # and finalizes. rollout_pipeline_depth is moot here (host work
                # already runs on actor threads/processes); continuous_batching
                # selects the actors' engine path.
                self._collect_async(num_rollouts, elements, stats, acc)
            elif continuous:
                self._collect_continuous(num_rollouts, depth, elements, stats, acc)
            elif depth > 0:
                self._collect_pipelined(num_rollouts, depth, elements, stats, acc)
            else:
                self._collect_serial(num_rollouts, elements, stats, acc)

            with self.obs.span("collect/finalize", stage="collection"):
                self.mean_kl = acc["kl_sum"] / max(acc["kl_batches"], 1)
                stats["time/rollout_host"] = acc["host_s"]
                # the first step's gap starts here
                self._step_mark = tracing.mark()
                self._host_gap_t0 = self._step_mark["t"]
                total = self._host_gap_t0 - exp_time
                stats["time/exp"] = total
                # what the host and the runtime did meanwhile, on any thread
                attributed = attributed_between(mark, self._step_mark)
                stats.update(attributed)
                # the fenced generate spans' two halves, summed like every time/* key (the chunked
                # paths have them; actors and the slot-refill engine generate off this thread's clock)
                stats.setdefault("time/generate_dispatch", 0.0)
                stats.setdefault("time/generate_wait", 0.0)
                self._score_summary(stats, acc)
                # the collection's self time: wall time the main thread spent in
                # none of generate, reward_fn, or the wait for the scoring outputs
                # (defined on the chunked paths; actors and the slot-refill engine
                # generate off this thread's clock)
                stats["time/collect_host"] = max(
                    0.0, total - stats.get("time/generate", 0.0) - acc["blocked_s"]
                )
                # decode steps the sampler's loops ran (the longest row of each
                # chunk, from the response masks) and the fenced generate time per
                # step, prefill included
                stats["rollout/decode_steps"] = float(acc["decode_steps"])
                if acc["decode_steps"]:
                    stats["time/decode_step"] = (
                        stats.get("time/generate", 0.0) / acc["decode_steps"]
                    )
                # whole-collection aggregates with identical definitions in BOTH
                # modes (wall per chunk; generated tokens ÷ collection wall time) —
                # the benchmark suite's A/B report then measures real speedup, never
                # a per-mode metric redefinition
                stats["time/rollout"] = total / max(acc["chunks"], 1)
                if total > 0 and acc["gen_tokens"]:
                    stats["throughput/rollout_tokens_per_sec"] = acc["gen_tokens"] / total
                # slot accounting, uniform across modes (continuous batching already
                # set these from the engine's exact counters; the chunked paths
                # derive them from response masks — see docs/PERFORMANCE.md)
                if acc["slot_steps"]:
                    stats.setdefault(
                        "throughput/slot_utilization",
                        acc["live_slot_steps"] / acc["slot_steps"],
                    )
                    stats.setdefault(
                        "rollout/padded_decode_frac",
                        1.0 - acc["live_slot_steps"] / acc["slot_steps"],
                    )
                if "rollout/spec_rounds" in acc:  # a drafter's rounds, over the collection's chunks
                    for key in ("rollout/spec_rounds", "rollout/draft_proposed", "rollout/draft_accepted"):
                        stats[key] = float(acc[key])
                    stats["rollout/spec_acceptance_rate"] = acc["rollout/draft_accepted"] / max(acc["rollout/draft_proposed"], 1)
                    stats["rollout/tokens_per_round"] = acc["committed_live"] / max(acc["rollout/spec_live_row_rounds"], 1)
                    # the fenced generate time a round, prefill included (time/decode_step beside it: a TOKEN of the longest row)
                    stats["time/spec_round"] = stats.get("time/generate", 0.0) / max(acc["rollout/spec_rounds"], 1)
                # the share of the cache's slots the decode steps' (or rounds') attention read
                stats["rollout/kv_read_frac"] = (
                    acc["kv_slots_read"] / acc["kv_slots"] if acc["kv_slots"] else 1.0
                )
                if acc["kv_window_slots"]:  # the same, of the window layers alone
                    stats["rollout/kv_window_read_frac"] = (
                        acc["kv_window_slots_read"] / acc["kv_window_slots"]
                    )
                # rollout-side dynamics summaries + health canary (accumulated per
                # chunk in _rollout_chunk_finalize; setdefault keeps the engine's
                # exact counters when continuous batching already merged them)
                ref_hist = acc.get("ref_kl_hist")
                if ref_hist is not None:
                    stats.update(
                        self.obs.dynamics.summarize({"dist/ref_kl_hist": ref_hist})
                    )
                gen_lens = acc.get("gen_lens")
                if gen_lens:
                    stats.setdefault(
                        "rollout/gen_len_p50", float(np.percentile(gen_lens, 50))
                    )
                    stats.setdefault(
                        "rollout/gen_len_p95", float(np.percentile(gen_lens, 95))
                    )
                if acc.get("rep_total"):
                    stats.setdefault(
                        "rollout/repetition_frac", acc["rep_pairs"] / acc["rep_total"]
                    )
                self._collection_summary(stats, acc)
                if self.obs.setup.first_collect_s is None:
                    self.obs.setup.first_collect_s = total
                self._open_cycle(stats, attributed, exp_time)
                self.make_experience_stats = stats
                self.tracker.log(stats, step=iter_count)

                self.store.push(elements[:num_rollouts] if num_rollouts else elements)
                if self.log_rollouts:
                    self.store.export_history(location=self.rollout_logging_dir)

    def _collection_summary(
        self, stats: Dict[str, float], acc: Dict[str, float]
    ) -> None:
        """Hook 3 of 3: the reward and KL keys of a collection's record
        whose meaning is the algorithm's (``self.mean_kl`` is already the
        collection's). PPO's ``exp_scores/*`` and ``policy/sqrt_kl`` are the
        newest chunk's, set in :meth:`_chunk_element_fn`; the rollout health
        detectors read PPO's vocabulary (``policy/sqrt_kl`` against the
        controller target) and a trip dumps a triage batch through PPO's
        ``_triage_extra``, so the feed stays here."""
        stats["kl_ctl_value"] = self.kl_ctl.value
        self.obs.health.observe_rollout(stats)

    # ------------------------------------------------------------------
    # optimization
    # ------------------------------------------------------------------

    def loss_fn(
        self, params: Any, batch: Dict[str, jax.Array], rng: jax.Array
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """GAE + clipped PPO objective on a rollout minibatch (reference
        ``accelerate_ppo_trainer.py:136-207``)."""
        method: PPOConfig = self.config.method
        queries = batch["query_tensors"]
        responses = batch["response_tensors"]
        query_mask = batch["query_mask"]
        response_mask = batch["response_mask"].astype(jnp.float32)
        Q = queries.shape[1]
        R = responses.shape[1]

        old_logprobs = batch["logprobs"]
        old_values = batch["values"]
        rewards = batch["rewards"]

        advantages, returns = method.get_advantages_and_returns(
            old_values, rewards, response_mask
        )

        def method_loss(logprobs, values_pred):
            return method.loss(
                logprobs=logprobs,
                values=values_pred,
                old_logprobs=old_logprobs,
                old_values=old_values,
                advantages=advantages,
                returns=returns,
                mask=response_mask,
                behavior_logprobs=batch.get("behavior_logprobs"),
            )

        if self.is_seq2seq:
            B = queries.shape[0]
            start_id = self.tcfg.decoder_start_token_id
            dec_in = jnp.concatenate(
                [jnp.full((B, 1), start_id, jnp.int32), responses[:, :-1]], axis=1
            )
            dec_mask = jnp.concatenate(
                [jnp.ones((B, 1), jnp.int32), batch["response_mask"][:, :-1]], axis=1
            )
            out = self.module.apply(
                {"params": params},
                queries,
                attention_mask=query_mask,
                decoder_input_ids=dec_in,
                decoder_attention_mask=dec_mask,
            )
            logprobs = logprobs_of_labels(out["logits"], responses)
            values_pred = out["value"]
            loss, stats = method_loss(logprobs, values_pred)
            if method.dist_sketches:
                # entropy needs the full logits the method's loss never
                # sees — sketch it here while [B, R, V] is still live
                stats.update(
                    loss_sketches(
                        {"entropy": (entropy_of_logits(out["logits"]), response_mask)}
                    )
                )
            return self.with_router_aux((loss, stats), out)

        input_ids = jnp.concatenate([queries, responses], axis=1)
        attention_mask = jnp.concatenate(
            [query_mask, batch["response_mask"]], axis=1
        )
        out = self.module.apply(
            {"params": params}, input_ids, attention_mask=attention_mask,
            logits_span=(Q - 1, Q + R - 1),
        )
        logprobs = logprobs_of_labels(out["logits"], responses)
        values_pred = out["value"][:, Q - 1 : Q + R - 1]

        loss, stats = method_loss(logprobs, values_pred)
        if method.dist_sketches:
            # entropy needs the full logits the method's loss never sees —
            # sketch it here while the [B, R, V] span is still live
            stats.update(
                loss_sketches(
                    {"entropy": (entropy_of_logits(out["logits"]), response_mask)}
                )
            )
        return self.with_router_aux((loss, stats), out)

    def _learner_loader(self):
        """The store's minibatches under the learner's pad policy
        (``PPORolloutStorage.create_loader``): rows grouped by length, each
        minibatch padded to a rung of a ladder of widths that follows from
        the job's own length budget (``trlx.py::train`` truncates the prompts
        to the same one) and is fixed for the run. Both ladders are passed by
        name, so a caller's default for either length cannot replace them."""
        self._step_ladders = self._length_ladders()
        return self.store.create_loader(
            self.config.train.batch_size,
            shuffle=True,
            seed=self.config.train.seed,
            query_length=self._step_ladders[0],
            response_length=self._step_ladders[1],
        )

    def _length_ladders(self) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """The ladders of query and response widths the job's length budget
        implies: the learner's loader pads a minibatch to their rungs, and
        the scoring forward cuts a length group of a chunk to a query rung.
        Known from the configuration alone, so before the first collection."""
        new = int(self._resolve_gen_config()[0].max_new_tokens)
        return length_ladder(int(self.config.train.seq_length) - new), length_ladder(new)

    def _planned_step_shape(self, batch: Any) -> bool:
        items = batch._asdict() if hasattr(batch, "_asdict") else batch
        queries, responses = self._step_ladders
        return (
            items["query_tensors"].shape[1] in queries
            and items["response_tensors"].shape[1] in responses
        )

    def prepare_learning(self) -> None:
        self.train_dataloader = self._learner_loader()
        self.n_updates_per_batch = self.config.method.ppo_epochs
        self.total_steps = min(
            self.config.train.total_steps,
            self.config.train.epochs
            * self.n_updates_per_batch
            * len(self.train_dataloader),
        )

    def _triage_programs(self) -> Tuple[Callable, Callable]:
        """The two programs of :meth:`_triage_extra`, built once and kept, so
        that a dump finds at a shape it has run at what it compiled there."""
        if self._triage_fns is None:
            module = self.module

            def response_logprobs(params, batch):
                queries, responses = batch["query_tensors"], batch["response_tensors"]
                Q, R = queries.shape[1], responses.shape[1]
                out = module.apply(
                    {"params": params},
                    jnp.concatenate([queries, responses], axis=1),
                    attention_mask=jnp.concatenate(
                        [batch["query_mask"], batch["response_mask"]], axis=1
                    ),
                    logits_span=(Q - 1, Q + R - 1),
                )
                return logprobs_of_labels(out["logits"], responses)

            self._triage_fns = (
                self.programs.program(
                    "get_advantages_and_returns", self.config.method.get_advantages_and_returns),
                self.programs.program("response_logprobs", response_logprobs),
            )
        return self._triage_fns

    def _warm_triage(self, batch: Any) -> None:
        """A fresh value head explains none of the returns, so
        ``value_ev_collapse`` trips at the end of the health window of every
        run with one, and its dump needs two programs: they are built here, at
        the first optimizer step, on the rows a dump of this batch would hold.
        A cycle of fewer steps than the window would otherwise compile them in
        the middle of its second (PERF.md section 6, PR 49). A trainer with no
        value head compiles them when something trips."""
        if self.model_head != "value" or not self.obs._trace_dir or jax.process_index() != 0:
            return
        try:
            self._triage_extra(self._triage_rows(batch))
        except Exception:  # pragma: no cover - defensive: triage never stops a run
            logger.warning("triage warm-up failed", exc_info=True)

    def _triage_extra(self, arrays: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """Derived per-token quantities for a triaged batch: GAE advantages/
        returns, plus the new-policy per-token logprob deltas from one
        forward under the current params (best-effort — a sick enough state
        can fail the forward, and the tokens/masks already dumped are the
        irreplaceable part). Both are jitted: op by op the forward of a 1.2 B
        model held the loop for 12 s on a v5e (PERF.md section 6, PR 30)."""
        extra: Dict[str, np.ndarray] = {}
        values = arrays.get("values")
        rewards = arrays.get("rewards")
        mask = arrays.get("response_mask")
        advantages_and_returns, response_logprobs = self._triage_programs()
        try:
            if values is not None and rewards is not None and mask is not None:
                adv, ret = advantages_and_returns(
                    jnp.asarray(values),
                    jnp.asarray(rewards),
                    jnp.asarray(mask, jnp.float32),
                )
                extra["advantages"] = np.asarray(adv)
                extra["returns"] = np.asarray(ret)
        except Exception:  # pragma: no cover - defensive, crash-path code
            pass
        tokens = ("query_tensors", "response_tensors", "query_mask", "response_mask")
        try:
            if not self.is_seq2seq and all(k in arrays for k in tokens + ("logprobs",)):
                new_logprobs = response_logprobs(
                    self.state.params, {k: jnp.asarray(arrays[k]) for k in tokens}
                )
                extra["logprob_deltas"] = np.asarray(new_logprobs) - np.asarray(
                    arrays["logprobs"]
                )
        except Exception:  # pragma: no cover - defensive, crash-path code
            pass
        return extra

    def post_backward_callback(self) -> None:
        # adaptive KL coefficient folds into the next compiled rollout as a
        # scalar argument (reference ``accelerate_ppo_trainer.py:233-234``)
        self.kl_ctl.update(self.mean_kl, n_steps=self.config.train.batch_size)
        skips = getattr(self.kl_ctl, "skipped", 0)
        if skips:
            # non-finite chunk KLs the controller refused to fold in
            # (models/ppo.py AdaptiveKLController.update)
            self.obs.metrics.set_gauge("health/kl_ctl_skips", float(skips))

    def post_epoch_callback(self) -> None:
        # fresh rollouts with the updated policy (reference ``:222-231``)
        self.store.clear_history()
        self.make_experience(self.config.method.num_rollouts, self.iter_count)
        with self.obs.span("learn/loader", stage="create"):
            self.train_dataloader = self._learner_loader()

    # ------------------------------------------------------------------
    # which rows share a scoring forward (docs/PERFORMANCE.md)
    # ------------------------------------------------------------------
    #
    # This section stands behind loss_fn, and what it needed above keeps the
    # line counts there: a Mosaic kernel's compile-cache key holds its callers'
    # file paths and LINES (score_rows, score_fn and loss_fn are such callers;
    # PERF.md section 6, PR 47), so moving them would make every cell compile
    # its scoring program and train step again behind a parent that has them.

    def _score_groups(self, prompt_mask) -> Optional[list]:
        """:func:`score_groups` of a chunk under this job's minibatch and
        query ladder; ``None``: the chunk is scored whole (a seq2seq chunk
        always: its prompts are the encoder's side)."""
        if self.is_seq2seq:
            return None
        mask = np.asarray(prompt_mask)
        return score_groups(
            mask.sum(axis=1), mask.shape[1], int(self.config.train.batch_size),
            score_rungs(self._length_ladders()[0]),
        )

    def _note_score_slots(self, chunk: Dict[str, Any], acc: Dict[str, float]) -> None:
        """One chunk's real tokens (its two masks) and the slots its scoring
        programs were fed, into the collection's sums."""
        prompt_mask, response_mask = chunk["prompt_mask"], chunk["response_mask"]
        (B, P), N = prompt_mask.shape, response_mask.shape[1]
        groups = self._score_groups(prompt_mask)
        slots = B * (P + N) if groups is None else sum(len(take) * (width + N) for take, width in groups)
        acc["score_slots"] = acc.get("score_slots", 0) + slots
        acc["score_tokens"] = acc.get("score_tokens", 0) + int(prompt_mask.sum()) + int(response_mask.sum())

    def _score_summary(self, stats: Dict[str, float], acc: Dict[str, float]) -> None:
        """The collection record's two keys on the scoring forward's shapes:
        the share of padding in what it was fed, and how many distinct
        ``(rows, prompt width, new tokens)`` it has a program for (each built
        at its first dispatch; the twin of ``learn/step_shapes``)."""
        if acc.get("score_slots"):
            stats["collect/score_pad_frac"] = 1.0 - acc["score_tokens"] / acc["score_slots"]
        stats["collect/score_shapes"] = float(len(self._score_fns))


def score_rungs(ladder: Tuple[int, ...]) -> Tuple[int, ...]:
    """The rungs of the learner's query ladder the scoring forward cuts its
    length groups to: the first and the last. A scoring program a rung is
    set-up (trace, lower, cache load: 1.0 + 0.8 + 0.3 s each on the chip's
    host), and ``setup_s`` is an end-to-end metric. Measured on a v5e, warm
    starts, parent and change alternating, six pairs (PERF.md section 6, PR
    48): with all three rungs of the hh cells (256, 512, 896; 19,456 slots a
    chunk where the whole chunk is 32,768) the median ``setup_s`` of
    ``gptj6b_ppo_hh`` went 47.17 -> 52.54 s, the first cycle's part of it
    26.0 -> 29.2 s: over the 5% ISSUE 48 allowed. The first and last rungs
    (22,528 slots) keep three quarters of the slots saved for one more
    program in place of two. A constant with its measurement, not a setting."""
    return tuple(ladder) if len(ladder) < 3 else (ladder[0], ladder[-1])


def score_groups(lengths, width: int, rows: int, ladder) -> Optional[list]:
    """Which rows of a chunk share a scoring forward, and at which prompt width.

    ``lengths`` are the prompts' real tokens a row, ``width`` the slots the
    chunk's prompts are left-padded to. The rows are stable-sorted by length
    and cut into groups of ``rows`` (the learner's minibatch, so a group runs
    at a ``(rows, width)`` the train step runs at too); a group's width is the
    smallest rung of ``ladder`` (:func:`score_rungs` of the learner's query
    ladder, ``pipeline/ppo_pipeline.py::length_ladder``) that holds its longest prompt,
    and never more than ``width``: left padding is cut off, none is added.
    Returns ``[(row indices, width), ...]``, or ``None`` where nothing is to be
    gained: every group needs the chunk's own width, or the chunk is no whole
    number of groups. The chunk is then scored whole, in the one program it
    always had. Chunks of one width (a rung) have at most one program a rung.

    Rows do not interact inside the scoring forward (positions come from the
    mask's cumulative sum, so left padding cut off moves nothing), with one
    exception this adds no new case of: an expert layer with a capacity limit
    drops by what else is in its batch, so a row's score there depends on its
    group, as it already depends on its chunk and on ``score_row_groups``'
    pieces. Every configured cell is dropless."""
    lengths = np.asarray(lengths)
    if rows < 1 or len(lengths) == 0 or len(lengths) % rows:
        return None
    order = np.argsort(lengths, kind="stable").astype(np.int32).reshape(-1, rows)
    # a group's last row is its longest
    rungs = [pad_length([range(int(lengths[take[-1]]))], tuple(ladder)) for take in order]
    widths = [min(rung or width, width) for rung in rungs]
    if all(w == width for w in widths):
        return None
    return list(zip(order, widths))


def group_prompts(prompt_ids, prompt_mask, take, width: int) -> Dict[str, np.ndarray]:
    """On the host: the prompts of the rows ``take``, the left padding beyond
    ``width`` slots cut off, under the names of the scoring program's first
    two arguments (:func:`group_rows` appends the responses to the first)."""
    cut = np.asarray(prompt_mask).shape[1] - width
    return {"sequences": np.asarray(prompt_ids)[take, cut:], "prompt_mask": np.asarray(prompt_mask)[take, cut:]}


def group_rows(prompt_ids, prompt_mask, response_tokens, response_mask, take):
    """Inside the scoring program of one length group: the group's prompts
    (cut to the group's width on the host) beside the chunk's responses give
    ``score_rows``' four arrays for the rows ``take``."""
    response_tokens, response_mask = response_tokens[take], response_mask[take]
    sequences = jnp.concatenate([prompt_ids, response_tokens], axis=1)
    return sequences, prompt_mask, response_tokens, response_mask


def scores_in_chunk_order(host: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """A chunk's landed scoring outputs, ``[B, N]`` each in the chunk's own
    row order, whether one dispatch made them or one a length group."""
    if "groups" not in host:
        return host
    back = np.argsort(np.concatenate(host["takes"]))
    return {k: np.concatenate([g[k] for g in host["groups"]])[back] for k in host["groups"][0]}
