"""Shared TPU trainer: model/optimizer setup, jitted train step, generation,
eval loop, checkpointing, trackers.

Behavioral parity target: ``AccelerateRLTrainer``
(``trlx/trainer/accelerate_base_trainer.py:39-574``) — same control flow
(epochs → batches → n updates per batch, interval checkpoints, best-reward
checkpoint, eval with optional gen-kwarg sweep, stop-sequence trimming), but
the torch/Accelerate machinery is replaced by the TPU-native stack: one
global ``Mesh``, GSPMD-sharded params, a jitted ``value_and_grad`` step with
donated train state, and jitted KV-cache generation (``trlx_tpu/ops/sampling``).

The reference's per-rank device dance (``pad_across_processes``/``gather``/
``scatter``, ``accelerate_ppo_trainer.py:292-327``) does not exist here:
arrays are globally sharded, so "gather to rank 0" is just ``jax.device_get``
at the host boundary (reward/metric fns), and per-rank scatter is
``shard_batch`` placement.
"""

import dataclasses
import json
import os
from abc import abstractmethod
import statistics
from collections import deque
from contextlib import ExitStack, closing
from time import perf_counter, time
from typing import Any, Callable, Dict, List, Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.tokenizer import from_config as tokenizer_from_config
from trlx_tpu.models.builder import (
    build_causal_lm,
    grad_param_frac,
    is_frozen,
    trainable_mask,
)
from trlx_tpu.models.transformer import (
    block_selected_pairs, block_selected_steps, make_kv_cache, selected_frac, sparse_gather_rows,
)
from trlx_tpu.ops.cache_layout import CONV, INDEX, KV, LATENT, LINEAR, POOLED, RECURRENT, cache_bytes, cache_slots, cacheless, kv_lane_heads, ring
from trlx_tpu.ops.paged_kv import kv_bytes
from trlx_tpu.ops.sampling import (
    GenerationConfig,
    GenerationOutput,
    generate,
    generate_seq2seq,
    kv_extents,
)
from trlx_tpu.parallel import make_mesh, set_global_mesh, shard_batch
from trlx_tpu.pipeline import BasePipeline
from trlx_tpu.trainer import BaseRLTrainer
from trlx_tpu.utils import (
    Clock,
    filter_non_scalars,
    get_optimizer,
    get_scheduler,
    significant,
    to_host,
)
from trlx_tpu.utils import logging
from trlx_tpu.utils.checkpoint import (
    is_committed,
    newest_committed_checkpoint,
    prune_checkpoints,
    read_extra,
    save_pretrained,
    save_state,
    wait_for_saves,
)
from trlx_tpu.observability import Observability, Span, tracing, train_step_flops
from trlx_tpu.observability import mfu as obs_mfu
from trlx_tpu.resilience import UPDATE_OK_KEY, Resilience, TrainingPreempted
from trlx_tpu.utils.programs import ProgramStore, array_bytes
from trlx_tpu.utils.trackers import make_tracker

logger = logging.get_logger(__name__)

# Bad-batch triage bounds (docs/OBSERVABILITY.md "Training dynamics"): cap
# rows per dump and dumps per run so a persistently-tripping detector can't
# fill the disk with repro artifacts.
TRIAGE_MAX_ROWS = 64
TRIAGE_MAX_DUMPS = 8


@flax.struct.dataclass
class TrainState:
    """Functional train state threaded through the jitted step."""

    params: Any
    opt_state: Any
    step: jax.Array  # scalar int32
    rng: jax.Array


# An interval (a cycle; a step of one width) that takes more than this many
# times the median of the newest SLOW_HISTORY of its kind gets one line in the
# log, with what the host and the runtime did in it. The stalled cycles of
# PERF.md section 6 ran 1.4 to 2.7 times a normal one (2 to 8 s on 3 to 17)
# and the long train steps 1.25 to 1.45 times (40 to 70 ms on 155 to 190),
# while a window's normal cycles differ by under 2%. No verdict before
# SLOW_MIN_HISTORY intervals: the first of a kind compiles.
SLOW_INTERVAL_RATIO = 1.25
SLOW_HISTORY = 64
SLOW_MIN_HISTORY = 3

# parts of an interval in which the host waits for the device
_WAIT_PARTS = ("generate wait", "score wait", "train_step wait")


@dataclasses.dataclass
class _Flight:
    """A train step between its launch and its landing. The learn loop holds
    at most two: the one it lands and the one launched ahead of that."""

    step: int  # the update's index: iter_count when it lands
    batch: Any  # its host batch, held until it lands (the record, a triage dump)
    stats: Any  # the program's stat outputs, futures
    t_open: float  # when the launch's span opened
    dispatch: float  # seconds inside the launch
    ahead: bool  # launched before the previous step had landed
    last_replay: bool  # its batch's last: post_backward_callback follows the landing
    first_of_job: bool  # the launch built (or loaded) the train step
    hidden: float = 0.0  # time/step_host_hidden, set by the landing before it
    discarded: bool = False  # a rollback landed on the step before it


@dataclasses.dataclass
class _LearnLoop:
    """What one ``learn()`` call's launches and landings share."""

    step_host: ExitStack  # holds the open `learn/step_host` span
    clock: Clock
    tbar: Any
    results: Dict[str, Any]  # the newest evaluation's
    finished: bool = False  # a landing reached total_steps and closed the run


class _Lookahead:
    """An iterator one can look one item ahead on."""

    def __init__(self, iterable):
        self._it = iter(iterable)
        self._held: List[Any] = []

    def __iter__(self):
        return self

    def __next__(self):
        return self._held.pop() if self._held else next(self._it)

    def peek(self, default=None):
        """The item ``next()`` will return, drawn now; ``default`` at the end."""
        if not self._held:
            try:
                self._held.append(next(self._it))
            except StopIteration:
                return default
        return self._held[0]

    def close(self) -> None:
        self._it.close()


def _is_ready(tree: Any) -> bool:
    """Whether a program's outputs are there (they become ready together)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return not leaves or getattr(leaves[0], "is_ready", lambda: True)()


def attributed_between(m0: Dict[str, float], m1: Dict[str, float]) -> Dict[str, float]:
    """What a collection or step record says of the host and the runtime in
    its interval, from the sink's marks (``tracing.mark``) at its two ends:
    garbage collections on any thread (they stop every thread), seconds in
    tracing plus lowering, seconds in the backend's compile (cache loads
    included), the marking thread's CPU seconds, involuntary context
    switches and major page faults, and the whole process's CPU seconds and
    switches (the runtime's own threads launch the programs)."""
    d = tracing.since(m0, m1)
    return {
        "host/gc_pause_s": d.get("host/gc", 0.0),
        "host/gc_gen2": float(d.get("host/gc_gen2", 0)),
        "runtime/retrace_s": d.get("runtime/trace", 0.0) + d.get("runtime/lower", 0.0),
        "runtime/compile_s": d.get("runtime/compile", 0.0) + d.get("runtime/cache_load", 0.0),
        "host/cpu_s": d["host/cpu_s"],
        "host/invol_switches": float(d["host/invol_switches"]),
        "host/major_faults": float(d["host/major_faults"]),
        "host/proc_cpu_s": d["host/proc_cpu_s"],
        "host/proc_invol_switches": float(d["host/proc_invol_switches"]),
    }


def slow_interval_line(label: str, seconds: float, median: float, parts: Dict[str, float],
                       history: List[Tuple[float, Dict[str, float]]],
                       programs: List[str]) -> Tuple[str, str]:
    """The log line for an interval that ran long, and its verdict: ``gc``,
    ``retrace``, ``host dispatch``, ``thread not running`` or ``fence wait``.
    ``parts`` holds the interval's timed pieces by name beside the
    ``host/*`` and ``runtime/*`` keys of its record; each piece is set
    against its median over ``history``."""
    timed = [k for k in parts if "/" not in k and k != "off cpu"]
    over = {k: parts[k] - statistics.median(h[1].get(k, 0.0) for h in history) for k in parts}
    gc_s = parts["host/gc_pause_s"]
    retrace = parts["runtime/retrace_s"] + parts["runtime/compile_s"]
    waited = sum(over[k] for k in timed if k in _WAIT_PARTS)
    busy = sum(over[k] for k in timed if k not in _WAIT_PARTS)
    causes = {
        "gc": gc_s,
        "retrace": retrace,
        "thread not running": over["off cpu"],
        "host dispatch": busy - gc_s - retrace - over["off cpu"],
        "fence wait": waited,
    }
    verdict = max(causes, key=causes.get)
    top = sorted(timed, key=over.get, reverse=True)[:3]
    line = (
        f"{label}: {seconds:.2f} s against a median of {median:.2f}: "
        + ", ".join(f"{k} {over[k]:+.2f}" for k in top)
        + f", gc {gc_s:.2f}, retrace {retrace:.2f} ({', '.join(programs) or 'none'}), "
        f"cpu {parts['host/cpu_s']:.1f} s (process {parts['host/proc_cpu_s']:.1f}), "
        f"{int(parts['host/invol_switches'])} involuntary switches (process "
        f"{int(parts['host/proc_invol_switches'])}), {int(parts['host/major_faults'])} major "
        f"faults: {verdict}"
    )
    return line, verdict


def _optimizer_state_shardings(mesh, params: Any, abstract_opt: Any) -> Any:
    """Sharding pytree for an optimizer state, matched *structurally*: optax
    moment trees mirror the params pytree, so an opt-state leaf whose path
    suffix is a param path (and whose shape agrees) takes that param's
    sharding. Blockwise-quantized int8 moments (``codes``/``scales`` under a
    param path) shard their block dim over the largest dividing combination
    of the fsdp/model axes; everything else (counts, schedules) replicates.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from trlx_tpu.parallel.sharding import _axis_size, path_keys

    replicated = NamedSharding(mesh, PartitionSpec())
    param_by_path = {
        path_keys(path): (leaf.shape, leaf.sharding)
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
    }

    def leaf_sharding(path, leaf):
        keys = path_keys(path)
        # longest suffix first: the full opt path carries wrapper prefixes
        # (inner_states/<label>/0/mu/...) before the mirrored param path
        for start in range(len(keys)):
            hit = param_by_path.get(keys[start:])
            if hit is not None and hit[0] == leaf.shape:
                return hit[1]
        if keys and keys[-1] in ("codes", "scales") and len(leaf.shape) == 2:
            for axes in (("fsdp", "model"), ("fsdp",), ("model",)):
                size = _axis_size(mesh, axes)
                if size > 1 and leaf.shape[0] % size == 0:
                    spec = axes if len(axes) > 1 else axes[0]
                    return NamedSharding(mesh, PartitionSpec(spec, None))
        return replicated

    return jax.tree_util.tree_map_with_path(leaf_sharding, abstract_opt)


class TPUBaseTrainer(BaseRLTrainer):
    """Shared learn-loop trainer over a global device mesh.

    Subclasses define:

    - ``model_head``: ``None`` | ``"value"`` | ``"ilql"`` — which wrapper to
      build;
    - ``loss_fn(params, batch, rng) -> (loss, stats)``: a *pure* function of
      the param tree and a dict-of-arrays batch (closed over configs/module);
    - ``prepare_learning()``: set ``train_dataloader``, ``eval_dataloader``,
      ``n_updates_per_batch``, ``total_steps``;
    - optionally ``post_backward_callback`` / ``post_epoch_callback`` and
      ``adjust_logits_fn`` (on-device sampling-logit reshaping, e.g. ILQL).
    """

    model_head: Optional[str] = None

    def __init__(
        self,
        config: TRLConfig,
        reward_fn: Optional[Callable] = None,
        metric_fn: Optional[Callable] = None,
        stop_sequences: Optional[List[str]] = None,
        abstract_init: bool = False,
        **kwargs,
    ):
        # abstract_init: build the trainer with ShapeDtypeStruct weights —
        # no parameter/optimizer arrays are ever materialized, but every
        # jitted program (train step, generate, score) can still be lowered
        # and compiled for cost/memory analysis (trlx_tpu/perf.py). Such a
        # trainer can trace but never execute.
        self.abstract_init = abstract_init
        super().__init__(config, reward_fn, metric_fn, stop_sequences, **kwargs)
        if config.train.batch_size % max(1, config.train.grad_accum) != 0:
            raise ValueError(
                f"train.batch_size ({config.train.batch_size}) must be divisible "
                f"by train.grad_accum ({config.train.grad_accum})"
            )
        if config.engine.prefix_cache and config.engine.backend != "paged":
            # fail at construction, not at the first rollout collection
            # (and never silently: with continuous_batching off this knob
            # would otherwise just do nothing)
            raise ValueError(
                "engine.prefix_cache: true requires engine.backend: paged — "
                "dense per-slot KV caches cannot share blocks"
            )
        if config.engine.decode_kernel not in ("xla", "pallas"):
            raise ValueError(
                f"unknown engine.decode_kernel "
                f"'{config.engine.decode_kernel}' (xla | pallas)"
            )
        if (
            config.engine.decode_kernel == "pallas"
            and config.engine.backend != "paged"
        ):
            raise ValueError(
                "engine.decode_kernel: pallas is the in-place *paged* "
                "decode kernel (ops/paged_attention.py) — it requires "
                "engine.backend: paged"
            )
        if config.engine.prefill_kernel not in ("xla", "pallas"):
            raise ValueError(
                f"unknown engine.prefill_kernel "
                f"'{config.engine.prefill_kernel}' (xla | pallas)"
            )
        if (
            config.engine.prefill_kernel == "pallas"
            and config.engine.backend != "paged"
        ):
            raise ValueError(
                "engine.prefill_kernel: pallas is the in-place *paged* "
                "prefill kernel (ops/paged_prefill.py) — it requires "
                "engine.backend: paged"
            )
        if int(config.engine.prefill_chunk) < 0:
            raise ValueError(
                f"engine.prefill_chunk {config.engine.prefill_chunk} "
                "must be >= 0 (0 = monolithic prefill)"
            )
        if int(config.engine.prefill_chunk) and config.engine.backend != "paged":
            raise ValueError(
                "engine.prefill_chunk (chunked-prefill scheduling) "
                "requires engine.backend: paged — the chunk programs "
                "commit prompt spans through the block table"
            )
        if int(config.engine.speculative) < 0:
            raise ValueError(
                f"engine.speculative {config.engine.speculative} must be "
                ">= 0 (0 = off, k = draft tokens proposed per verify round)"
            )
        if int(config.engine.speculative):
            # each requirement its own error: the composition has three
            # independent preconditions and "speculative engine misconfigured"
            # would send users grepping
            if not config.model.draft_model_path:
                raise ValueError(
                    "engine.speculative (speculative continuous batching) "
                    "requires model.draft_model_path — the engine needs a "
                    "draft model to propose tokens for the target to verify"
                )
            if config.engine.backend != "paged":
                raise ValueError(
                    "engine.speculative requires engine.backend: paged — "
                    "the verify pass commits accepted K/V through the "
                    "block table with drop-mode writes"
                )
            # NOTE: no decode_kernel restriction — the spec segment's verify
            # pass runs the multi-position paged kernel in place
            # (ops/paged_attention.py::paged_verify_attention), so
            # engine.speculative composes with decode_kernel: pallas
        if config.serve.enabled:
            # each precondition its own error (docs/SERVING.md): the
            # serving frontend is built on block-table operations
            if config.engine.backend != "paged":
                raise ValueError(
                    "serve.enabled requires engine.backend: paged — token "
                    "streaming snapshots and priority preemption are "
                    "block-table operations"
                )
            if not getattr(config.train, "continuous_batching", False):
                raise ValueError(
                    "serve.enabled requires train.continuous_batching: "
                    "true — the serving engine is a ContinuousEngine built "
                    "through the slot-refill program cache"
                )
            if int(config.serve.slots) < 1:
                raise ValueError(
                    f"serve.slots {config.serve.slots} must be >= 1"
                )
            if not 0 <= int(config.serve.reserve_slots) < int(config.serve.slots):
                raise ValueError(
                    f"serve.reserve_slots {config.serve.reserve_slots} must "
                    f"leave at least one unreserved slot of serve.slots "
                    f"{config.serve.slots}"
                )
            if float(config.serve.drain_timeout_s) <= 0:
                raise ValueError(
                    f"serve.drain_timeout_s {config.serve.drain_timeout_s} "
                    "must be > 0 (the graceful-drain window)"
                )
            if int(config.serve.host_tier_blocks) and not config.engine.prefix_cache:
                raise ValueError(
                    "serve.host_tier_blocks requires engine.prefix_cache: "
                    "true — only committed prefix entries ever spill to the "
                    "host tier"
                )
            from trlx_tpu.engine.core import SERVE_CLASSES as _SC

            if config.serve.default_class not in _SC:
                raise ValueError(
                    f"unknown serve.default_class "
                    f"{config.serve.default_class!r} (expected one of {_SC})"
                )
        # the serving frontend (trlx_tpu/serve/, docs/SERVING.md); built in
        # learn() when serve.enabled, drained in _shutdown_collectors
        self._serve = None
        # runtime observability: span tracer, metrics registry, recompile/
        # memory watchdogs, profiler window (docs/OBSERVABILITY.md). First, so
        # that what building the model traces and compiles lands under a span
        self.obs = Observability(config)
        self.mesh = make_mesh(config.parallel)
        set_global_mesh(self.mesh)  # model code reads this for sequence-parallel ops
        # the job's own programs, kept compiled from one start to the next by
        # what the code can observe of the job (utils/programs.py)
        self.programs = ProgramStore(
            config, classes=(*type(self).__mro__, type(config.method)), mesh=self.mesh
        )
        # NOTE: the global mesh is process-wide; entry points re-assert it so
        # two trainers in one process don't trace against each other's mesh
        with self.obs.span("setup/tokenizer"):
            self.tokenizer = tokenizer_from_config(config.tokenizer)

        with self.obs.span("setup/init_model") as init_sp:
            two_qs = bool(getattr(config.method, "two_qs", True))
            # seq2seq (T5) vs causal arch selection (reference ``get_arch``,
            # ``accelerate_ppo_trainer.py:120-134``)
            self.is_seq2seq = config.model.model_arch_type == "seq2seq"
            if self.is_seq2seq:
                from trlx_tpu.models.builder import build_seq2seq_lm, seq2seq_trainable_mask

                build, mask_fn = build_seq2seq_lm, seq2seq_trainable_mask
            else:
                build, mask_fn = build_causal_lm, trainable_mask
            self.module, params, self.tcfg = build(
                config.model,
                config.parallel,
                head=self.model_head,
                two_qs=two_qs,
                seed=config.train.seed,
                abstract=abstract_init,
                mesh=self.mesh,
                programs=self.programs,
            )
            self.programs.extend(repr(self.tcfg), self.model_head, two_qs)
            self.param_mask = mask_fn(params, self.tcfg, config.model.num_layers_unfrozen)
            self.draft_module = self.draft_params = self.draft_tcfg = None
            # a model that publishes a next-token-prediction module (tcfg.mtp_layers)
            # drafts its rollouts with it: the model's own key decides, no option does
            self.self_drafts = bool(getattr(self.tcfg, "mtp_layers", 0))
            if self.self_drafts and config.model.draft_model_path:
                raise ValueError(
                    f"model.draft_model_path {config.model.draft_model_path!r} beside a model that drafts with its "
                    f"own next-token-prediction module (mtp_layers {self.tcfg.mtp_layers}, model_type "
                    f"{self.tcfg.model_type!r}): one drafter a sampler, and the module is the model's own; unset "
                    "model.draft_model_path"
                )
            self.last_spec_stats: Dict[str, float] = {}
            self.last_cache_stats: Dict[str, float] = {}
            # the serial dense sampler's static cache extents for the newest
            # generate() call (ops/sampling.py::kv_extents); None on the paths
            # that read every slot (seq2seq, speculative)
            self.last_kv_extents: Optional[Tuple[int, ...]] = None
            # beside them, each layer's cache slots and whether it has a window
            # (a window layer's cache is a ring of min(S, window) slots)
            self.last_kv_layers: Optional[Tuple[Tuple[int, bool], ...]] = None
            # the cache pytree's shapes by (config, rows, slots), for the gauges
            self._kv_cache_shapes: Dict[Tuple[Any, int, int], Any] = {}
            # the newest generate() call's span: duration, dispatch, wait
            self.last_generate_span: Optional[Span] = None
            # where the host gap before the next train step began (perf_counter):
            # the end of the last step's fence, or of the collection before it
            self._host_gap_t0: Optional[float] = None
            if config.model.draft_model_path and self.is_seq2seq:
                logger.warning(
                    "model.draft_model_path is ignored for seq2seq models: "
                    "speculative decoding is implemented for causal LMs only"
                )
            elif config.model.draft_model_path:
                from trlx_tpu.data.configs import ModelConfig as _MC

                # the draft always runs UNPIPELINED: under a pipe>1 mesh it
                # computes replicated across stages while the pipelined target
                # verifies its proposals (per-row cache depths flow through the
                # microbatch schedule via parallel/pipeline.py's cache_index
                # slicing)
                draft_extra = dict(config.model.draft_model_extra_kwargs)
                draft_extra["ignore_pipe_mesh"] = True
                self.draft_module, self.draft_params, self.draft_tcfg = build_causal_lm(
                    _MC(
                        model_path=config.model.draft_model_path,
                        model_extra_kwargs=draft_extra,
                    ),
                    config.parallel,
                    head=None,
                    seed=config.train.seed + 1,
                    abstract=abstract_init,
                    mesh=self.mesh,
                    programs=self.programs,
                )
                self.programs.extend(repr(self.draft_tcfg))
                if self.draft_tcfg.vocab_size != self.tcfg.vocab_size:
                    raise ValueError(
                        f"draft vocab {self.draft_tcfg.vocab_size} != policy vocab "
                        f"{self.tcfg.vocab_size}: speculative decoding needs a "
                        "same-tokenizer draft"
                    )

            default_lr = config.optimizer.kwargs.get("lr")
            self.schedule = get_scheduler(
                config.scheduler.name, dict(config.scheduler.kwargs), default_lr=default_lr
            )
            self.optimizer = get_optimizer(
                config.optimizer.name,
                dict(config.optimizer.kwargs),
                schedule=self.schedule,
                mask=self.param_mask,
            )
            # Optimizer state gets *explicit* shardings: moment tensors follow
            # their parameter's sharding (FSDP: ZeRO-sharded optimizer state),
            # quantized int8 moments shard their block dim, scalars/bookkeeping
            # replicate. Without out_shardings the compiler may leave the whole
            # state on one device — and checkpoint restore then commits that
            # placement, breaking later steps.
            from jax.sharding import NamedSharding, PartitionSpec

            replicated = NamedSharding(self.mesh, PartitionSpec())

            def init_counters(seed):
                rollout_rng, state_rng = jax.random.split(jax.random.PRNGKey(seed))
                return jnp.zeros((), jnp.int32), state_rng, rollout_rng

            optimizer = self.optimizer

            def init_state(p, seed):
                return (optimizer.init(p), *init_counters(seed))

            # one program for everything of the state but the params: the
            # moments, the step counter and both rng streams. The seed is an
            # argument (models/builder.py::_build_params says why)
            seed = np.int64(config.train.seed)
            if abstract_init:
                opt_state = jax.eval_shape(optimizer.init, params)
                counters = self.programs.program(
                    "init_counters", init_counters, once=True, out_shardings=replicated)(seed)
            else:
                # the moments' shapes, which their shardings follow, are
                # traced where the program is (on a miss of the store)
                opt_state, *counters = self.programs.program(
                    "init_state", init_state, once=True,
                    jit_kwargs=lambda: {"out_shardings": (
                        _optimizer_state_shardings(
                            self.mesh, params, jax.eval_shape(optimizer.init, params)),
                        replicated, replicated, replicated,
                    )},
                )(params, seed)
            step, state_rng, self._rollout_rng = counters
            self.state = TrainState(
                params=params, opt_state=opt_state, step=step, rng=state_rng
            )
        self.obs.setup.init_model_s = init_sp.duration

        # generation settings (reference: accelerate_base_trainer.py:176-198)
        self.generate_kwargs = dict(config.method.gen_kwargs)
        self.generate_experience_kwargs = (
            dict(config.method.gen_experience_kwargs)
            if getattr(config.method, "gen_experience_kwargs", None)
            else None
        )
        self._generate_fns: Dict[Any, Callable] = {}
        self._train_step_fn: Optional[Callable] = None
        # parameters the train step differentiates over parameters held
        # (learn/grad_param_frac; set where the step is built)
        self._grad_param_frac = 1.0
        self._step_shapes: set = set()  # batch shapes the train step has run at
        self._last_batch_host: Any = None
        self._last_batch_sharded: Any = None
        # the host batch of the step being landed: what a triage dump holds
        # (a step launched ahead may have placed the next one meanwhile)
        self._landing_batch: Any = None

        # resilience: preemption handler, update guard, host-call hardening,
        # fault plan (docs/RESILIENCE.md). Shares the metrics registry so
        # every resilience/* counter rides the tracker stream. reward_fn is
        # wrapped ONCE here, hardening every call site (rollouts, eval).
        self.resilience = Resilience(config, metrics=self.obs.metrics)
        self.reward_fn = self.resilience.harden_reward_fn(
            self.reward_fn, seed=config.train.seed
        )
        self.tracker = self.resilience.harden_tracker(
            make_tracker(config), seed=config.train.seed
        )
        self._train_step_flops: Optional[float] = None
        self._flops_thread = None
        self.eval_pipeline: Optional[BasePipeline] = None
        self.iter_count = 0
        self.nth_evaluation = 0
        self.best_reward = -float("inf")
        self._emergency_resume = False
        self._prompt_chunks_drawn = 0
        self._triage_dumps = 0
        self._triage_fns: Optional[Tuple[Callable, Callable]] = None  # ppo.py::_triage_programs
        # the sink's totals where the host gap began (tracing.mark): a step
        # record carries the difference to its own fence
        self._step_mark: Optional[Dict[str, float]] = None
        # the newest intervals of each kind (a cycle; a step of one width),
        # seconds and parts, for the slow-interval line
        self._intervals: Dict[Any, deque] = {}
        self._cycle: Optional[Dict[str, Any]] = None  # the cycle being added up

    # ------------------------------------------------------------------
    # subclass contract
    # ------------------------------------------------------------------

    @abstractmethod
    def loss_fn(
        self, params: Any, batch: Dict[str, jax.Array], rng: jax.Array
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        ...

    def _resolved_logit_chunk(self) -> int:
        """``method.logit_chunk`` when the module can stream the vocab
        projection, else 0 — warning ONCE (and before any forward runs, so
        DPO's whole-dataset reference precompute isn't silently full-size)."""
        chunk = getattr(self.config.method, "logit_chunk", 0)
        if not chunk:
            return 0
        if hasattr(type(self.module), "project_logits"):
            return chunk
        if not getattr(self, "_warned_logit_chunk", False):
            self._warned_logit_chunk = True
            logger.warning(
                "method.logit_chunk=%d is IGNORED: %s has no project_logits — "
                "the full [B, T, V] logits will be materialized",
                chunk,
                type(self.module).__name__,
            )
        return 0

    def with_router_aux(
        self,
        loss_stats: Tuple[jax.Array, Dict[str, Any]],
        out: Any,
    ) -> Tuple[jax.Array, Dict[str, Any]]:
        """Fold the MoE router auxiliary losses (Switch load-balance +
        ST-MoE z-loss, weighted by the model config's ``router_aux_coef`` /
        ``router_z_coef``) into a trainer loss. No-op for dense backbones —
        every ``loss_fn`` routes its return through here so any trainer can
        drive a mixture-of-experts policy."""
        loss, stats = loss_stats
        kda = out.get("kda_stats") if isinstance(out, dict) else None
        if kda is not None:  # layers under a gated delta rule (KDAMixer): what its chunked form must survive, how hard it writes, and whether the pass took the kernel
            from trlx_tpu.ops.delta_rule import scan_takes_kernel

            head = self.tcfg.kda_head_dim
            stats = dict(stats, **{"learn/kda_log_decay_min": kda[0], "learn/kda_beta_mean": kda[1]})
            stats["learn/kda_scan_pallas"] = float(scan_takes_kernel(head, head))
        gate = out.get("attn_gate_mean") if isinstance(out, dict) else None
        if gate is not None:  # a headwise gate on the latent layers' output: neither shut nor open
            stats = dict(stats, **{"learn/attn_gate_mean": gate})
        aux = out.get("router_aux_loss") if isinstance(out, dict) else None
        if aux is None:
            return loss, stats
        tcfg = self.tcfg
        new_loss = (
            loss
            + getattr(tcfg, "router_aux_coef", 0.0) * aux[0]
            + getattr(tcfg, "router_z_coef", 0.0) * aux[1]
        )
        stats = dict(stats)
        stats["losses/router_load_balance"] = aux[0]
        stats["losses/router_z"] = aux[1]
        load = out.get("router_load")
        if load is not None:
            stats["moe/dropped_frac"] = load[0]
            stats["moe/load_max_over_mean"] = load[1]
            if load.shape[0] > 2:  # layers that hold a share of their experts
                stats["moe/held_frac"] = load[2]
                stats["moe/held_load_max_over_mean"] = load[3]
                stats["moe/compact_frac"] = load[4]
        shared = out.get("router_shared")
        if shared is not None:  # layers with a shared expert beside the routed ones
            stats["moe/shared_row_frac"] = shared[0]
            stats["moe/chosen_score_mean"] = shared[1]
        # keep the logged total in sync with what is actually optimized.
        # Contract: every method.loss must report its headline total under
        # one of these canonical keys (PPO/ILQL/GRPO/DPO flatten to
        # losses/total_loss, SFT to losses/loss) — a new method using a
        # different name would log a total that excludes the router terms
        for key in ("losses/total_loss", "losses/loss"):
            if key in stats:
                stats[key] = new_loss
        return new_loss, stats

    @abstractmethod
    def prepare_learning(self) -> None:
        ...

    def post_backward_callback(self) -> None:
        pass

    def post_epoch_callback(self) -> None:
        pass

    def adjust_logits_fn(self, extra_kwargs: Dict[str, Any]) -> Optional[Callable]:
        """On-device hook reshaping last-token logits during sampling.

        ``extra_kwargs`` are the gen kwargs not consumed by
        :class:`GenerationConfig` (e.g. ILQL's ``beta``) — resolved per
        ``generate`` call, so kwarg overrides and eval sweeps reach the hook.

        Contract: ``fn(step_out, logits) -> logits`` must be polymorphic
        over leading dims. The plain sampler passes last-position views
        (``[B, ...]`` fields, ``[B, V]`` logits); the speculative sampler
        passes the verify block (``[B, G+1, ...]`` fields, ``[B, G+1, V]``
        logits) with the same keys (model outputs + ``last_tokens``). Hooks
        that broadcast per-position fields against the trailing vocab axis
        — like ILQL's — satisfy this automatically; hooks that reshape
        assuming a fixed rank do not and must not be paired with a draft
        model.
        """
        return None

    def add_eval_pipeline(self, eval_pipeline: BasePipeline) -> None:
        self.eval_pipeline = eval_pipeline

    # ------------------------------------------------------------------
    # train step
    # ------------------------------------------------------------------

    def _build_train_step(self) -> Callable:
        optimizer = self.optimizer
        schedule = self.schedule
        accum = max(1, int(getattr(self.config.train, "grad_accum", 1)))

        # Pin the output state's shardings to the input state's (explicit
        # out_shardings below). Without the pin, output shardings are
        # reconstructed from XLA's canonicalized HloShardings, which strip
        # size-1 mesh axes from specs (P('fsdp','model') → P() on a dp-only
        # mesh): the step-1 output state then hashes differently from the
        # step-1 input and step 2 silently recompiles the entire program —
        # one full extra XLA compile and a second resident executable every
        # run. Found by the recompile watchdog (observability/watchdogs.py).
        from jax.sharding import NamedSharding

        if all(
            isinstance(getattr(leaf, "sharding", None), NamedSharding)
            for leaf in jax.tree_util.tree_leaves(self.state)
        ):
            state_shardings = jax.tree_util.tree_map(
                lambda leaf: leaf.sharding, self.state
            )
        else:  # abstract_init analysis trainers carry no real shardings
            state_shardings = None

        # Update guard (docs/RESILIENCE.md): with a policy other than "off",
        # the step checks isfinite(global_norm) ON DEVICE — any NaN/inf in
        # loss, grads, or activations propagates into the norm, which is
        # already computed for gradients/global_norm. The flag rides back in
        # the stats dict the learn loop fetches anyway: zero extra host
        # syncs. Only the "skip" policy also SELECTS the old params/opt
        # state on device — the select keeps both state versions live, which
        # defeats donation's in-place update (≈2× train-step temp memory;
        # visible in benchmarks/perf_budgets.json). "rollback"/"halt" need
        # only the flag: the host restores a committed checkpoint / raises,
        # so their train step keeps the donated, guard-free memory profile.
        guard_policy = self.resilience.guard.policy
        guard_flag = guard_policy != "off"
        guard_select = guard_policy == "skip"

        # No gradient is taken with respect to a leaf the mask freezes (the
        # bool False: the blocks under num_layers_unfrozen, the whole base under
        # LoRA, ILQL's target-Q heads, a seq2seq encoder), as the reference's
        # requires_grad False takes none: the loss sees such a leaf through
        # stop_gradient, so no weight gradient is computed for it, nothing is
        # kept for one, and no backward pass runs below the lowest trained leaf
        # (wte trains in a causal job, so activation gradients still cross its
        # frozen blocks). gradients/global_norm is then the trained leaves'
        # norm; the optimizer sends the frozen leaves' zeros to set_to_zero as
        # it always did. A per-layer 0/1 vector (h_scan under scan_layers) is
        # differentiated whole and masked in the optimizer. A mask that freezes
        # nothing leaves the program as it was.
        mask = self.param_mask
        if not any(is_frozen(m) for m in jax.tree_util.tree_leaves(mask)):
            mask = None
        self._grad_param_frac = grad_param_frac(self.state.params, mask)

        def scaled_loss(params, batch, rng, loss_scale):
            # loss_scale is 1.0 outside fault injection — an exact identity
            # multiply (IEEE x*1.0 == x bitwise) — and NaN when the plan
            # poisons this step, making loss AND grads non-finite
            if mask is not None:
                params = jax.tree_util.tree_map(
                    lambda p, m: jax.lax.stop_gradient(p) if is_frozen(m) else p, params, mask
                )
            loss, stats = self.loss_fn(params, batch, rng)
            return loss * loss_scale, stats

        def grads_of(params, batch, rng, loss_scale):
            return jax.value_and_grad(scaled_loss, has_aux=True)(
                params, batch, rng, loss_scale
            )

        def accumulated_grads(params, batch, step_rng, loss_scale):
            """lax.scan over ``accum`` microbatches; grads and stats averaged.

            Whitening/running statistics inside ``loss_fn`` see one
            microbatch at a time (same as the reference under DeepSpeed
            accumulation, where each micro forward is independent).
            """
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
                batch,
            )
            rngs = jax.random.split(step_rng, accum)
            # zero-init the carry from eval_shape so the model's fwd+bwd is
            # traced exactly once (inside the scan body) — peeling the first
            # microbatch would duplicate the whole HLO graph
            first = jax.tree_util.tree_map(lambda x: x[0], micro)
            (_, stats_sh), grads_sh = jax.eval_shape(
                grads_of, params, first, rngs[0], loss_scale
            )
            zeros = lambda tree: jax.tree_util.tree_map(  # noqa: E731
                lambda s: jnp.zeros(s.shape, s.dtype), tree
            )

            def body(carry, xs):
                grads_acc, stats_acc = carry
                mb, r = xs
                (_, stats_i), grads_i = grads_of(params, mb, r, loss_scale)
                grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc, grads_i)
                stats_acc = jax.tree_util.tree_map(jnp.add, stats_acc, stats_i)
                return (grads_acc, stats_acc), None

            (grads, stats), _ = jax.lax.scan(
                body, (zeros(grads_sh), zeros(stats_sh)), (micro, rngs)
            )
            grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
            stats = jax.tree_util.tree_map(lambda s: s / accum, stats)
            # per-trainer loss key varies; callers only consume stats
            return (jnp.zeros(()), stats), grads

        # named for the device trace: the program is `jit_train_step` there
        def train_step(state: TrainState, batch: Dict[str, jax.Array], loss_scale):
            rng, step_rng = jax.random.split(state.rng)
            if accum == 1:
                (loss, stats), grads = grads_of(
                    state.params, batch, step_rng, loss_scale
                )
            else:
                (loss, stats), grads = accumulated_grads(
                    state.params, batch, step_rng, loss_scale
                )
            updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
            stats = dict(stats)
            stats["learning_rate"] = (
                schedule(state.step) if callable(schedule) else schedule
            )
            gnorm = optax.global_norm(grads)
            stats["gradients/global_norm"] = gnorm
            step_inc = 1
            if guard_flag:
                ok = jnp.isfinite(gnorm)
                if accum == 1:
                    ok = ok & jnp.isfinite(loss)
                stats["resilience/update_ok"] = ok.astype(jnp.float32)
            if guard_select:
                # scalar select per leaf: when the check fails, the update
                # (and the step counter driving the LR schedule) is dropped
                # on device — the poison batch never touches the weights
                params = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(ok, n, o), params, state.params
                )
                opt_state = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(ok, n, o), opt_state, state.opt_state
                )
                step_inc = ok.astype(jnp.int32)
            new_state = TrainState(
                params=params,
                opt_state=opt_state,
                step=state.step + step_inc,
                rng=rng,
            )
            return new_state, stats

        if state_shardings is not None:
            # stats stay unspecified (None): XLA picks, as before
            return self.programs.program(
                "train_step", train_step, donate_argnums=(0,), out_shardings=(state_shardings, None)
            )
        return self.programs.program("train_step", train_step, donate_argnums=(0,))

    def _drop_batch_memo(self) -> None:
        """Release the memoized sharded batch (one batch of HBM) once its
        replay window is over — before rollout collection / final eval."""
        self._last_batch_host = None
        self._last_batch_sharded = None
        self._landing_batch = None

    def _maybe_prefetch(self, loader, depth: Optional[int] = None):
        """Wrap a loader in background-thread prefetch (``depth`` batches
        ahead, default ``train.prefetch_batches``) so collation overlaps the
        device step — the reference's DataLoader-worker capability."""
        if depth is None:
            depth = getattr(self.config.train, "prefetch_batches", 0)
        if depth and depth > 0 and loader is not None:
            from trlx_tpu.pipeline import PrefetchLoader

            return PrefetchLoader(loader, depth)
        return loader

    def _maybe_prefetch_prompts(self, loader):
        """Prompt-side seam of :meth:`_maybe_prefetch`, gated on the rollout
        pipeline depth (``train.rollout_pipeline_depth``): prompt collation
        runs ahead on a background thread so ``next(prompt_iterator)`` never
        stalls the chunk dispatch loop in ``make_experience``. One worker
        preserves batch order, so rollout determinism is unaffected."""
        depth = int(getattr(self.config.train, "rollout_pipeline_depth", 0) or 0)
        return self._maybe_prefetch(loader, depth)

    def _count_prompt_chunks(self, iterator):
        """Wrap the (infinite) prompt iterator so every chunk the trainer
        consumes advances ``_prompt_chunks_drawn``. Emergency checkpoints
        record the count and resume replays exactly that many draws
        (:meth:`load`), so the prompt stream — and the loader's per-epoch
        shuffle RNG behind it — sits precisely where an uninterrupted run
        would have it. Without this, the first post-resume collection trains
        on the *initial* prompts again and the trajectory silently forks."""
        for chunk in iterator:
            self._prompt_chunks_drawn += 1
            yield chunk

    def _attn_tile_walk(self, width: int) -> Tuple[float, float, float]:
        """``(visited fraction, key tile, interior fraction)`` of the flash
        forward in a step of this width: (query block, key block) pairs it
        visits over the pairs causal attention has, the key tile's slots,
        and the visited pairs that run the body without positional masks,
        summed over the layers. Static arithmetic from each layer's layout
        (``TransformerConfig.layer_layouts``) and the tile the kernel itself
        chooses at this width (``ops/flash_attention.py::choose_blocks``);
        the first is 1 where no window binds."""
        from trlx_tpu.ops.flash_attention import block_pairs_visited, choose_blocks

        layouts = getattr(self.tcfg, "layer_layouts", None)
        if not layouts or not width:
            return 1.0, 0.0, 0.0
        block_q, block_k = choose_blocks(width, width)
        pairs = [block_pairs_visited(width, layout.window, block_q, block_k) for layout in layouts if layout.mixer == "attention"]
        visited, causal, interior = (sum(p[i] for p in pairs) for i in range(3))
        return visited / max(causal, 1), float(block_k), interior / max(visited, 1)

    def _batch_token_counts(self, batch: Any) -> Tuple[int, int, int]:
        """``(real, fed, width)`` of a host batch: the unpadded tokens its
        masks count (so padding doesn't inflate
        ``throughput/tokens_per_sec``), the rows × width slots the step is
        fed (``learn/pad_frac`` is one minus their ratio) and the slots of
        one row (``learn/step_width``: query plus response width under PPO)."""
        items = batch._asdict() if hasattr(batch, "_asdict") else batch
        if not isinstance(items, dict):
            return 0, 0, 0
        if "attention_mask" in items:
            masks = [items["attention_mask"]]
        else:
            masks = [
                v for k, v in items.items() if k.endswith("mask") and hasattr(v, "sum")
            ]
        if masks:
            return (
                int(sum(np.asarray(m).sum() for m in masks)),
                int(sum(np.asarray(m).size for m in masks)),
                int(sum(np.asarray(m).shape[-1] for m in masks)),
            )
        for v in items.values():
            if hasattr(v, "shape") and len(v.shape) >= 2:
                fed = int(v.shape[0] * v.shape[1])
                return fed, fed, int(v.shape[1])
        return 0, 0, 0

    def _export_observability(self) -> None:
        """Best-effort span export (``trace.json``) next to the tracker's
        stats — never allowed to fail a training run."""
        try:
            paths = self.obs.export()
            if paths:
                logger.info(f"wrote span trace: {paths['trace']}")
        except Exception as e:  # pragma: no cover - defensive
            logger.warning(f"span trace export failed: {e}")

    def _note_state_bytes(self) -> None:
        """The memory account's state terms (docs/OBSERVABILITY.md "The memory
        account"): host arithmetic over the trees' shapes, once when learning
        is prepared and again when a restore replaces the state."""
        self.obs.memory.note_state(
            self.state.params, self.state.opt_state, getattr(self, "ref_params", None))

    def train_step(self, batch: Dict[str, np.ndarray], step: Optional[int] = None) -> Dict[str, Any]:
        """Launch one optimization step on a host batch: place the batch (or
        reuse its placed copy), enqueue the program, return its stat outputs
        as futures. ``self.state`` is the step's new state from here on, a
        future too. ``step`` is the update's index, ``iter_count`` unless the
        learn loop launches it ahead of the previous step's landing.

        The sharded device copy is memoized on the batch object: the PPO
        inner loop replays the same batch ``ppo_epochs`` times
        (``n_updates_per_batch``), and one host→device transfer serves all
        replays."""
        set_global_mesh(self.mesh)
        if step is None:
            step = self.iter_count
        plan = self.resilience.plan
        if (
            plan
            and jax.process_index() == jax.process_count() - 1
            and plan.poll("sleep_one_proc", step=step)
        ):
            # deterministic straggler: stall the LAST rank's step so the
            # cluster-telemetry watchdog has something real to flag
            # (cluster/straggler_rank; docs/OBSERVABILITY.md)
            from time import sleep as _sleep

            from trlx_tpu.resilience.faults import SLEEP_FAULT_S

            logger.warning(
                f"fault plan: sleeping {SLEEP_FAULT_S}s inside update "
                f"{step} (injected straggler)"
            )
            _sleep(SLEEP_FAULT_S)
        first_step = self._train_step_fn is None
        if first_step:
            self._train_step_fn = self._build_train_step()
        if batch is self._last_batch_host:
            arrays = self._last_batch_sharded
        else:
            items = batch._asdict() if hasattr(batch, "_asdict") else batch
            with self.obs.span("learn/loader", stage="shard"):
                arrays = shard_batch(
                    {k: v for k, v in items.items() if hasattr(v, "ndim")}, self.mesh
                )
            self._last_batch_host = batch
            self._last_batch_sharded = arrays
        self.state, stats = self._train_step_fn(self.state, arrays, self._loss_scale(step))
        if first_step:  # set-up: the device is on its first step meanwhile
            self._warm_triage(batch)
        # recompile watchdog: a warm train step retracing (shape/dtype
        # drift) is invisible otherwise — it just gets slow. The first
        # compile of a shape the trainer's pad policy planned is expected
        shape = tuple(sorted((k, tuple(v.shape)) for k, v in arrays.items()))
        self.obs.recompile.observe(
            "train_step",
            self._train_step_fn,
            planned=shape if self._planned_step_shape(batch) else None,
        )
        self._step_shapes.add(shape)
        self.obs.metrics.set_gauge("learn/step_shapes", float(len(self._step_shapes)))
        return stats

    def _planned_step_shape(self, batch: Any) -> bool:
        """Whether ``batch`` has a shape the trainer's pad policy set out
        before the run (PPO and GRPO: a rung of each ladder of widths), so
        that the train step's first compile for it is no shape drift."""
        return False

    def _loss_scale(self, step: Optional[int] = None) -> np.float32:
        """1.0, or NaN when the fault plan poisons update ``step``'s loss
        (``nan_loss@step:N`` — deterministic update-guard exercise). Traced
        as a scalar array argument, so both values share one compiled
        program and the clean-path multiply is an exact identity."""
        plan = self.resilience.plan
        if step is None:
            step = self.iter_count
        if plan and plan.poll("nan_loss", step=step):
            logger.warning(
                f"fault plan: poisoning the loss of update {step} to NaN"
            )
            return np.float32(np.nan)
        return np.float32(1.0)

    def _ensure_train_step_flops(
        self, arrays: Optional[Dict[str, jax.Array]], wait: bool = False
    ) -> Optional[float]:
        """Per-device flops of the compiled train step (for MFU), computed
        once per trainer from the exact program via ``perf.lowered_costs``.

        The AOT lower+compile does not share the jit call path's executable
        cache, so it runs on a daemon thread — the hot loop never stalls on
        a duplicate XLA compile; ``throughput/mfu`` simply appears in the
        stats stream once the analysis lands (typically a few steps in).
        ``None`` while pending, unavailable, or disabled (``TRLX_TPU_MFU=0``)."""
        if (
            self._train_step_flops is None
            and self._flops_thread is None
            and self._train_step_fn is not None
            and arrays is not None
        ):
            import threading

            # abstract twins are built HERE (metadata only): the worker must
            # not hold the live state/batch arrays across later donations
            abstract = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=getattr(x, "sharding", None)
                ),
                (self.state, arrays, np.float32(1.0)),
            )

            def work(fn=self._train_step_fn, args=abstract):
                # -1 sentinel: tried and unavailable, don't retry
                self._train_step_flops = train_step_flops(fn, *args) or -1.0

            self._flops_thread = threading.Thread(
                target=work, name="trlx-tpu-flops", daemon=True
            )
            self._flops_thread.start()
        if (
            wait
            and self._flops_thread is not None
            and self._train_step_flops is None
        ):
            # end-of-run join: short runs still report a final MFU; a
            # still-compiling analysis on a big model gives up after the
            # timeout rather than stalling exit
            self._flops_thread.join(timeout=120.0)
        flops = self._train_step_flops
        return flops if flops is not None and flops > 0 else None

    # ------------------------------------------------------------------
    # generation
    # ------------------------------------------------------------------

    @property
    def draft_gamma(self) -> int:
        """Tokens proposed a round: one a next-token-prediction module where the
        model drafts with its own, else ``model.draft_gamma``."""
        return int(self.tcfg.mtp_layers if self.self_drafts else self.config.model.draft_gamma)

    def _apply_fn(self):
        module = self.module

        def apply_fn(params, input_ids, **kw):
            return module.apply({"params": params}, input_ids, **kw)

        return apply_fn

    def _compose_logit_mask(self, adjust: Optional[Callable]) -> Optional[Callable]:
        """Chain the trainer's transition ``logit_mask`` after any algorithm
        logit reshaping: tokens whose ``mask[last_token, next_token]`` is
        False sample with −inf logits. Masks smaller than the vocab disallow
        out-of-range *next* tokens; out-of-range *last* tokens (no transition
        row exists for them) sample unconstrained rather than borrowing an
        unrelated row's constraints."""
        mask = self._logit_mask_array()
        if mask is None:
            return adjust
        from trlx_tpu.ops.sampling import apply_transition_mask

        def fn(step_out: Dict[str, Any], logits: jax.Array) -> jax.Array:
            if adjust is not None:
                logits = adjust(step_out, logits)
            return apply_transition_mask(mask, step_out["last_tokens"], logits)

        return fn

    def _logit_mask_array(self) -> Optional[jax.Array]:
        """The trainer's transition logit mask as a bool device array (one
        conversion for the step-sampler hook and the speculative path)."""
        if self.logit_mask is None:
            return None
        return jnp.asarray(np.asarray(self.logit_mask), bool)

    def _get_generate_fn(
        self, gen_config: GenerationConfig, extra_kwargs: Tuple[Tuple[str, Any], ...] = ()
    ) -> Callable:
        key = (gen_config, extra_kwargs)
        if key not in self._generate_fns:
            algo_adjust = self.adjust_logits_fn(dict(extra_kwargs))
            if self.is_seq2seq:
                adjust = self._compose_logit_mask(algo_adjust)
                module = self.module
                start_id = self.tcfg.decoder_start_token_id

                def encode_fn(params, input_ids, attention_mask, max_len):
                    return module.apply(
                        {"params": params}, input_ids, attention_mask, max_len,
                        method=type(module).encode_for_decode,
                    )

                def decode_fn(params, dec_ids, enc_hidden, enc_mask, cache, cache_index):
                    # keywords: T5Transformer.decode has decoder_mask as its
                    # 4th positional arg; positional cache would mis-bind
                    return module.apply(
                        {"params": params}, dec_ids, enc_hidden, enc_mask,
                        cache=cache, cache_index=cache_index,
                        method=type(module).decode,
                    )

                def rollout_generate(params, input_ids, attention_mask, rng):
                    return generate_seq2seq(
                        encode_fn,
                        decode_fn,
                        params,
                        input_ids,
                        attention_mask,
                        rng,
                        gen_config,
                        start_token_id=start_id,
                        adjust_logits=adjust,
                    )

            elif self.draft_module is not None or self.self_drafts:
                # speculative decoding: draft proposes, the policy verifies
                # γ tokens per forward — lossless, so the rollout semantics
                # (tokens/logprobs/values under the policy) are unchanged.
                # Every sampler feature composes: the transition logit_mask
                # (applied to draft AND target), min_new_tokens (per-row
                # positional eos blocking), and the algo adjust hook (ILQL
                # reshaping — applied to the target's verify distributions;
                # a mismatched plain draft only costs acceptance rate).
                from trlx_tpu.ops.speculative import generate_speculative, module_drafter

                apply_fn = self._apply_fn()
                draft_module = self.draft_module
                draft_params = self.draft_params
                tcfg, dcfg = self.tcfg, self.draft_tcfg
                gamma = self.draft_gamma
                trans_mask = self._logit_mask_array()
                drafter = None  # a separate model behind draft_apply
                target_cache = lambda B, S: make_kv_cache(tcfg, B, S)
                draft_cache = lambda B, S: make_kv_cache(dcfg, B, S)

                def draft_apply(p, ids, **kw):
                    return draft_module.apply({"params": p}, ids, **kw)

                if self.self_drafts:
                    # the policy's own module, on the policy's own parameters: one
                    # cache list, the blocks' layers first and the module's behind them
                    module, L = self.module, tcfg.num_layers
                    drafter = module_drafter(
                        lambda p, hidden, next_ids, **kw: module.apply({"params": p}, hidden, next_ids, method="draft", **kw)
                    )
                    target_cache = lambda B, S: make_kv_cache(tcfg, B, S)[:L]
                    draft_cache = lambda B, S: make_kv_cache(tcfg, B, S)[L:]

                def rollout_generate(params, input_ids, attention_mask, rng):
                    # first arg is the target params, or the engine's
                    # (target, draft) tuple — the tuple form keeps draft
                    # params a traced operand instead of a closure, which
                    # abstract-weight lowering (trlx_tpu/perf.py) requires
                    if type(params) is tuple:
                        t_params, d_params = params
                    elif drafter is not None:
                        t_params = d_params = params
                    else:
                        t_params, d_params = params, draft_params
                    return generate_speculative(
                        apply_fn,
                        t_params,
                        draft_apply,
                        d_params,
                        target_cache,
                        draft_cache,
                        input_ids,
                        attention_mask,
                        rng,
                        gen_config,
                        gamma=gamma,
                        return_stats=True,
                        transition_mask=trans_mask,
                        adjust_logits=algo_adjust,
                        drafter=drafter,
                    )

            else:
                apply_fn = self._apply_fn()
                tcfg = self.tcfg
                adjust = self._compose_logit_mask(algo_adjust)

                def rollout_generate(params, input_ids, attention_mask, rng):
                    return generate(
                        apply_fn,
                        params,
                        lambda B, S: make_kv_cache(tcfg, B, S),
                        input_ids,
                        attention_mask,
                        rng,
                        gen_config,
                        adjust_logits=adjust,
                    )

            # the def's name is the program's (module `jit_rollout_generate`
            # in a device trace): trace reductions match on it. Kept by the memo
            # key and the bytes of the mask the closure bakes in; a separate
            # draft model's parameters are constants of the closure, which no
            # key sees, and such a program is not kept (utils/programs.py)
            if self.draft_module is not None and not self.is_seq2seq:
                self._generate_fns[key] = jax.jit(rollout_generate)
            else:
                self._generate_fns[key] = self.programs.program(
                    "rollout_generate", rollout_generate, repr(key), array_bytes(self.logit_mask)
                )
        return self._generate_fns[key]

    def _resolve_gen_config(
        self, eval_mode: bool = False, **kwargs
    ) -> Tuple[GenerationConfig, Tuple[Tuple[str, Any], ...]]:
        """Resolve (gen_config, extra_kwargs) the way :meth:`generate` does —
        the shared seam for the plain sampler and the continuous-batching
        engine, so both see identical sampling semantics. ``extra_kwargs``
        are the non-GenerationConfig kwargs (hashable, for the program
        caches and the ``adjust_logits_fn`` hook)."""
        base = (
            self.generate_kwargs
            if eval_mode or self.generate_experience_kwargs is None
            else self.generate_experience_kwargs
        )
        gen_kwargs = dict(base)
        gen_kwargs.update(kwargs)
        gen_config = GenerationConfig.from_gen_kwargs(
            gen_kwargs,
            eos_token_id=self.tokenizer.eos_token_id,
            pad_token_id=self.tokenizer.pad_token_id,
        )
        import dataclasses as _dc

        known = {f.name for f in _dc.fields(GenerationConfig)}
        extra_kwargs = tuple(
            sorted(
                (k, tuple(v) if isinstance(v, list) else v)
                for k, v in gen_kwargs.items()
                if k not in known
            )
        )
        return gen_config, extra_kwargs

    def _get_slot_refill_fns(
        self,
        gen_config: GenerationConfig,
        extra_kwargs: Tuple[Tuple[str, Any], ...],
        batch_size: int,
        prompt_len: int,
        segment_len: int,
    ):
        """Compiled slot-refill programs (refill prefill + segment decode)
        for one shape bucket — the continuous-batching analogue of
        :meth:`_get_generate_fn`, sharing its adjust-hook composition so the
        engine samples exactly what plain ``generate`` would."""
        if self.is_seq2seq:
            raise NotImplementedError(
                "train.continuous_batching supports causal LMs only: the "
                "seq2seq decoder has no slot-refill path"
            )
        gamma = int(self.config.engine.speculative)
        if gamma and self.draft_module is None:
            # __init__ validates the config path; this guards direct callers
            raise ValueError(
                "engine.speculative requires model.draft_model_path (no "
                "draft model was built)"
            )
        if self.draft_module is not None and not gamma:
            if not getattr(self, "_warned_cb_draft", False):
                self._warned_cb_draft = True
                logger.warning(
                    "model.draft_model_path is set but engine.speculative "
                    "is 0: continuous batching runs PLAIN decode segments "
                    "(the serial path's model.draft_gamma does not apply "
                    "here — set engine.speculative to propose k tokens "
                    "per verify round)"
                )
        import dataclasses as _dc

        gen_config = _dc.replace(gen_config, per_row_rng=True)
        paged = self._resolve_paged_spec(
            batch_size, prompt_len, gen_config, gamma=gamma
        )
        decode_kernel = (
            self.config.engine.decode_kernel if paged is not None else "xla"
        )
        prefill_kernel = (
            self.config.engine.prefill_kernel if paged is not None else "xla"
        )
        key = (
            "slot_refill", gen_config, extra_kwargs, batch_size, prompt_len,
            segment_len, paged, decode_kernel, prefill_kernel, gamma,
        )
        if key not in self._generate_fns:
            from trlx_tpu.ops.slot_refill import make_slot_refill_fns

            algo_adjust = self.adjust_logits_fn(dict(extra_kwargs))
            tcfg = self.tcfg
            spec_kwargs = {}
            if gamma:
                # speculative segments take the transition mask SEPARATELY
                # (applied to draft AND target inside the shared round, the
                # serial generate_speculative convention) and the raw algo
                # hook for the target's verify distributions — composing
                # the mask into adjust would leave the draft unconstrained
                # and the acceptance rule lossy under constrained sampling
                adjust = algo_adjust
                draft_module, dcfg = self.draft_module, self.draft_tcfg

                def draft_apply(p, ids, **kw):
                    return draft_module.apply({"params": p}, ids, **kw)

                spec_kwargs = dict(
                    speculative=gamma,
                    draft_apply=draft_apply,
                    init_draft_cache_fn=lambda B, S: make_kv_cache(dcfg, B, S),
                    transition_mask=self._logit_mask_array(),
                )
            else:
                adjust = self._compose_logit_mask(algo_adjust)
            self._generate_fns[key] = make_slot_refill_fns(
                self._apply_fn(),
                # (a block pool, and the rows and views that go to and from it, are never lane-packed: make_kv_cache)
                lambda B, S: make_kv_cache(tcfg, B, S, lane_packed=paged is None),
                batch_size,
                prompt_len,
                gen_config,
                adjust_logits=adjust,
                segment_len=segment_len,
                params_example=self.state.params,
                paged=paged,
                decode_kernel=decode_kernel,
                prefill_kernel=prefill_kernel,
                **spec_kwargs,
            )
        return self._generate_fns[key]

    def _engine_params(self, params: Any = None) -> Any:
        """The params object the rollout engines consume: the policy
        params, or — with ``engine.speculative`` on — the ``(target,
        draft)`` tuple the spec programs unpack. One object means
        ``swap_params`` adopts both trees atomically at a segment boundary
        (a mid-stream sync can never verify old-target against new-draft)."""
        target = self.state.params if params is None else params
        if int(self.config.engine.speculative):
            return (target, self.draft_params)
        return target

    def _resolve_paged_spec(
        self, batch_size: int, prompt_len: int, gen_config, gamma: int = 0
    ):
        """The paged-KV geometry for this trainer's ``engine:`` config
        section, or None for the dense backend. ``max_kv_blocks`` auto
        (0) sizes the pool so every slot can reach full length, plus an
        equal prefix-cache working set when the cache is on — lazy
        per-segment growth then keeps the *used* fraction at live tokens
        (docs/PERFORMANCE.md)."""
        ecfg = self.config.engine
        if ecfg.backend == "dense":
            return None
        if ecfg.backend != "paged":
            raise ValueError(
                f"unknown engine.backend '{ecfg.backend}' (dense | paged)"
            )
        from trlx_tpu.ops.paged_kv import PagedSpec, num_table_blocks

        bs = int(ecfg.kv_block_size)
        if bs < 1:
            raise ValueError(f"engine.kv_block_size {bs} must be >= 1")
        # speculative segments gather/scatter an S = P + N + gamma view
        # (solo's cache width — the G probe columns past the last commit),
        # so tables carry entries for the probe region too; only the
        # committable P + N columns ever consume allocated blocks
        table_blocks = num_table_blocks(
            prompt_len + gen_config.max_new_tokens + int(gamma), bs
        )
        max_blocks = int(ecfg.max_kv_blocks)
        if max_blocks <= 0:
            max_blocks = 1 + batch_size * table_blocks * (
                2 if self._prefix_cache_enabled() else 1
            )
        return PagedSpec(block_size=bs, max_blocks=max_blocks)

    def _prefix_cache_enabled(self) -> bool:
        """engine.prefix_cache, gated off (with a one-time warning) for
        capacity-routed MoE policies: expert capacity couples a row's tokens,
        so a suffix-only prefill is not bit-identical to the full prefill
        there."""
        if not self.config.engine.prefix_cache:
            return False
        if getattr(self.tcfg, "num_experts", 0) and self.tcfg.moe_capacity_factor > 0:
            # dropless routing (moe_capacity_factor 0) couples no two tokens
            if not getattr(self, "_warned_moe_prefix", False):
                self._warned_moe_prefix = True
                logger.warning(
                    "engine.prefix_cache disabled: MoE expert capacity is "
                    "shared across a sequence's tokens, so suffix-only "
                    "prefill would not be bit-identical to the full "
                    "prefill (set engine.prefix_cache: false to silence)"
                )
            return False
        return True

    def generate(
        self,
        input_ids: np.ndarray,
        attention_mask: Optional[np.ndarray] = None,
        eval_mode: bool = False,
        params: Optional[Any] = None,
        rng: Optional[jax.Array] = None,
        **kwargs,
    ) -> GenerationOutput:
        """Sample continuations for a left-padded prompt batch.

        Rollout generation uses ``gen_experience_kwargs`` when configured
        (reference ``generate`` vs ``generate_eval``,
        ``accelerate_base_trainer.py:228-253``).

        ``params``/``rng`` default to the trainer's own state — the async
        actor path (docs/ASYNC_RL.md) passes both explicitly: actors sample
        under channel-published param copies (never ``state.params``, whose
        buffers the donated train step invalidates), and a requeued chunk
        regenerates under its dispatched RNG.
        """
        set_global_mesh(self.mesh)
        gen_config, extra_kwargs = self._resolve_gen_config(eval_mode, **kwargs)
        input_ids = np.asarray(input_ids, np.int32)
        if attention_mask is None:
            attention_mask = (input_ids != self.tokenizer.pad_token_id).astype(np.int32)
        if rng is None:
            self._rollout_rng, rng = jax.random.split(self._rollout_rng)
        # fenced span: duration is device-true decode time, not dispatch
        # latency (nests under make_experience's "rollout" span). It opens
        # before the host-side set-up of the call (engine lookup, placing the
        # prompts): the device waits through that too
        with self.obs.span("generate", eval_mode=bool(eval_mode)) as sp:
            # the serial dense path behind the unified Engine interface
            # (trlx_tpu/engine/core.py) — the wrapped jitted program is
            # unchanged: it stays the bit-equivalence reference for the
            # continuous-batching and paged backends. The params-override path
            # (async actor threads) gets a PER-THREAD engine wrapper: engines
            # carry mutable `params`, and an actor generating concurrently with
            # the learner's eval on one shared wrapper would clobber each
            # other's params mid-call (the compiled program underneath is still
            # shared via _get_generate_fn's cache — wrappers are thin).
            if params is not None:
                import threading as _threading

                engine = self._get_serial_engine(
                    gen_config, extra_kwargs, tag=_threading.get_ident()
                )
                engine.params = params
            else:
                engine = self._get_serial_engine(gen_config, extra_kwargs)
            batch = shard_batch(
                {"input_ids": input_ids, "attention_mask": np.asarray(attention_mask, np.int32)},
                self.mesh,
            )
            # cleared up front so stats only ever reflect the *current* rollout
            # path — a draft-less or seq2seq generate must not keep reporting a
            # stale acceptance rate from an earlier speculative call
            self.last_spec_stats = {}
            self._note_dense_kv_gauge(input_ids.shape, gen_config)
            out = engine.generate(batch["input_ids"], batch["attention_mask"], rng)
            spec_stats = None
            if type(out) is tuple:  # speculative sampler: (output, stats) —
                # GenerationOutput itself is a NamedTuple, hence the exact check
                out, spec_stats = out
            sp.fence((out.sequences, out.response_tokens))
        if spec_stats is not None:
            # recorded for make_experience's stats (rollout observability:
            # the knob this informs is model.draft_gamma). Read AFTER the
            # fence has ended: landing the six scalars waits for the whole
            # program, and inside the span that wait read as dispatch
            # (generate_dispatch_ms 9578 in cell 9; PERF.md section 6, PR 51).
            # device_get already lands host scalars; no asarray needed
            spec_stats = jax.device_get(spec_stats)
            self.last_spec_stats = {
                "rollout/spec_acceptance_rate": float(spec_stats["acceptance_rate"]),
                "rollout/spec_rounds": int(spec_stats["rounds"]),
                # on live rows: a row that has ended still runs its rounds, and counts in neither
                "rollout/draft_proposed": int(spec_stats["proposed_draft_tokens"]),
                "rollout/draft_accepted": int(spec_stats["accepted_draft_tokens"]),
                "rollout/spec_live_row_rounds": int(spec_stats["live_row_rounds"]),
                "rollout/tokens_per_round": float(spec_stats["tokens_per_round"]),
            }
        self.last_generate_span = sp
        self.obs.recompile.observe("generate", engine._fn)
        return out

    def _get_serial_engine(self, gen_config, extra_kwargs, tag=None):
        """The SerialEngine wrapping this (config, kwargs)'s jitted rollout
        program — cached alongside the programs themselves; params are
        refreshed per call (the policy trains between collections).
        ``tag`` isolates wrappers per caller thread (async actors)."""
        key = ("serial_engine", gen_config, extra_kwargs, tag)
        if key not in self._generate_fns:
            from trlx_tpu.engine.core import SerialEngine

            self._generate_fns[key] = SerialEngine(
                self._get_generate_fn(gen_config, extra_kwargs),
                self.state.params,
                self.tokenizer.pad_token_id,
            )
        engine = self._generate_fns[key]
        engine.params = self.state.params
        return engine

    def _note_dense_kv_gauge(self, prompt_shape, gen_config) -> None:
        """``memory/kv_cache_bytes`` for the serial dense path and the collection record's ``rollout/*_bytes`` counters:
        the sampler allocates its cache inside the jitted program, so both are computed from the static shapes of that
        pytree (exact), by kind of leaf (``ops/cache_layout.py::cache_bytes``; this method maps its kinds to the record's
        keys, docs/OBSERVABILITY.md). The continuous-batching engines report their own measured gauge (EngineStats.metrics)."""
        self.last_kv_extents = self.last_kv_layers = None
        if self.is_seq2seq:
            return  # T5 cross/self caches have their own layout; not gauged
        B, P = prompt_shape
        S = P + gen_config.max_new_tokens
        drafts = self.draft_module is not None or self.self_drafts
        self.last_kv_extents = None if drafts else kv_extents(P, gen_config.max_new_tokens)

        def cache(tcfg, slots):  # one trace a shape: a walk at every call would be a retrace on every collection record
            key = (tcfg, B, slots)
            if key not in self._kv_cache_shapes:
                def kv_cache_shapes():  # named for the records (runtime/retrace_s)
                    return make_kv_cache(tcfg, B, slots)
                self._kv_cache_shapes[key] = jax.eval_shape(kv_cache_shapes)
            return self._kv_cache_shapes[key]

        policy_cache = cache(self.tcfg, S)
        held = cache_bytes(policy_cache, S)
        kv, latent = held[KV] + held[ring(KV)], held[LATENT] + held[ring(LATENT)]
        total = sum(held.values()) - held[RECURRENT] - held[LINEAR] - held[CONV]
        stats = self.last_cache_stats = {"rollout/kv_cache_bytes": float(kv), "rollout/ssm_state_bytes": float(held[RECURRENT]),
                                         "rollout/kv_lane_heads": float(kv_lane_heads(policy_cache, self.tcfg.dims_per_head))}
        if latent:  # the layers cache a latent in place of K and V (a window layer's ring apart), and index keys with it
            stats.update({"rollout/latent_cache_bytes": float(held[LATENT]), "rollout/index_cache_bytes": float(held[INDEX] + held[ring(INDEX)])})
        for kind, key in ((LINEAR, "rollout/linear_state_bytes"), (CONV, "rollout/conv_cache_bytes"), (POOLED, "rollout/kbar_cache_bytes"),
                          (ring(LATENT), "rollout/latent_ring_bytes")):
            if held[kind]:
                stats[key] = float(held[kind])
        empty = cacheless(policy_cache)  # layers without a sequence mixer: an empty dict each
        if empty:
            stats["rollout/cacheless_layers"] = float(empty)
        if held[POOLED]:  # attention layers under a block selection: the blocks a step keeps
            stats["rollout/attn_block_selected_frac"] = block_selected_steps(P, gen_config.max_new_tokens, self.tcfg)
        if getattr(self.tcfg, "index_topk", 0) and not drafts:  # rows of the cache a decode step gathers a row of the batch, all layers
            stats["rollout/sparse_gather_rows"] = float(sparse_gather_rows(self.tcfg, S))
        if not self.tcfg.scan_layers:
            layers = list(zip(policy_cache, self.tcfg.layer_layouts))  # (a layer whose whole cache is a state has no slots to read)
            self.last_kv_layers = tuple((int(cache_slots(layer)), layout.window is not None) for layer, layout in layers if cache_slots(layer))
            if len({layout.window for _, layout in layers}) > 1 and not latent:  # window layers beside global ones, K and V
                window = cache_bytes([layer for layer, layout in layers if layout.window is not None], S)
                stats["rollout/kv_cache_window_bytes"] = float(window[KV] + window[ring(KV)])
                stats["rollout/kv_cache_global_bytes"] = float(total - window[KV] - window[ring(KV)])
        if drafts:
            # target + draft caches, both S + gamma slots (ops/speculative.py); a model's own module has its layer in the model's own list
            spec_cache = cache(self.tcfg, S + self.draft_gamma)
            total = kv_bytes(spec_cache) + (0 if self.self_drafts else kv_bytes(cache(self.draft_tcfg, S + self.draft_gamma)))
            if self.self_drafts:
                stats["rollout/mtp_cache_bytes"] = float(kv_bytes(spec_cache[self.tcfg.num_layers :]))
        self.obs.metrics.set_gauge("memory/kv_cache_bytes", float(total))

    def generate_eval(self, input_ids, attention_mask=None, **kwargs) -> GenerationOutput:
        return self.generate(input_ids, attention_mask, eval_mode=True, **kwargs)

    # ------------------------------------------------------------------
    # decoding
    # ------------------------------------------------------------------

    def decode(
        self,
        prompt_ids: np.ndarray,  # [B, P] left-padded
        response_ids: np.ndarray,  # [B, N] right-padded
        append_eos_token: bool = False,
    ) -> Tuple[List[str], List[str], List[str]]:
        """Token batches → (samples, prompts, outputs) strings, trimming
        outputs at the first stop sequence and optionally re-appending eos
        (reference ``decode``, ``accelerate_base_trainer.py:200-226``)."""
        str_samples, str_prompts, str_outputs = [], [], []
        for prompt_row, response_row in zip(np.asarray(prompt_ids), np.asarray(response_ids)):
            str_prompt = self.tokenizer.decode(prompt_row.tolist(), skip_special_tokens=True)
            str_output = self.tokenizer.decode(response_row.tolist(), skip_special_tokens=True)
            if self.stop_sequences:
                for stop in self.stop_sequences:
                    result = str_output.split(stop)[0]
                    str_output = result
            if append_eos_token:
                str_output += self.tokenizer.eos_token
            str_prompts.append(str_prompt)
            str_outputs.append(str_output)
            if self.is_seq2seq:
                # seq2seq samples join prompt and output with the sep token
                # (reference ``decode``, ``accelerate_base_trainer.py:219-221``)
                sep = getattr(self.tokenizer, "sep_token", None) or " "
                str_samples.append(str_prompt + sep + str_output)
            else:
                str_samples.append(str_prompt + str_output)
        return str_samples, str_prompts, str_outputs

    # ------------------------------------------------------------------
    # evaluation
    # ------------------------------------------------------------------

    def evaluate(self) -> Dict[str, Any]:  # noqa: C901
        """Generate on eval prompts; score with reward/metric fns.

        Supports a single list-valued gen kwarg swept across generations
        (reference ``accelerate_base_trainer.py:286-428``).
        """
        set_global_mesh(self.mesh)
        logger.info("Evaluating model")
        stats: Dict[str, Any] = {}
        table_rows: List[List[Any]] = []

        sweep_key, sweep_values = None, [None]
        for k, v in self.generate_kwargs.items():
            if isinstance(v, list):
                sweep_key, sweep_values = k, v
                break

        eval_batch_size = self.config.train.eval_batch_size or self.config.train.batch_size
        loader = self.eval_pipeline.create_loader(eval_batch_size)

        for sweep_value in sweep_values:
            gen_overrides = {sweep_key: sweep_value} if sweep_key else {}
            all_prompts: List[str] = []
            all_outputs: List[str] = []
            all_samples: List[str] = []
            # device-true: every generate() call below fences on its outputs
            # at span exit, so this loop timer no longer reads dispatch
            gen_time = time()
            for batch in loader:
                out = self.generate_eval(
                    batch["input_ids"], batch["attention_mask"], **gen_overrides
                )
                prompt_ids = np.asarray(out.sequences)[:, : batch["input_ids"].shape[1]]
                response_ids = to_host(out.response_tokens)
                samples, prompts, outputs = self.decode(prompt_ids, response_ids)
                all_samples += samples
                all_prompts += prompts
                all_outputs += outputs
            stats["time/generate"] = time() - gen_time

            suffix = f"@{sweep_key}={sweep_value}" if sweep_key else ""
            if self.reward_fn:
                rewards = np.asarray(
                    self.reward_fn(
                        samples=all_samples, prompts=all_prompts, outputs=all_outputs
                    ),
                    dtype=np.float64,
                )
                stats[f"reward/mean{suffix}"] = float(rewards.mean())
                stats[f"reward/std{suffix}"] = float(rewards.std())
            else:
                rewards = [None] * len(all_samples)
            if self.metric_fn:
                metric_time = time()
                metrics = self.metric_fn(
                    samples=all_samples, prompts=all_prompts, outputs=all_outputs
                )
                stats["time/metric"] = time() - metric_time
                for name, values in metrics.items():
                    arr = np.asarray(values, dtype=np.float64)
                    stats[f"metrics/{name}{suffix}"] = (
                        float(arr.mean()) if arr.size else 0.0
                    )

            for i in range(min(len(all_prompts), 8)):
                row = [all_prompts[i], all_outputs[i]]
                if self.reward_fn:
                    row.append(significant(float(rewards[i])))
                if sweep_key:
                    row.append(sweep_value)
                table_rows.append(row)

        if jax.process_index() == 0 and table_rows:
            lines = ["prompt | output" + (" | reward" if self.reward_fn else "")]
            for row in table_rows[:8]:
                lines.append(" | ".join(str(c)[:80].replace("\n", "⏎") for c in row))
            logger.info("Eval samples:\n" + "\n".join(lines))

        self.nth_evaluation += 1
        return stats

    def _report_sweep(self, stats: Dict[str, Any]) -> None:
        """Write the latest eval stats to ``$TRLX_TPU_SWEEP_RESULT`` for the
        sweep runner — the subprocess analogue of the reference's Ray
        ``session.report`` (``accelerate_base_trainer.py:510-511``), written
        at every evaluation so interrupted trials still report."""
        path = os.environ.get("TRLX_TPU_SWEEP_RESULT")
        if not path or jax.process_index() != 0:
            return
        payload = {
            "iter_count": self.iter_count,
            "stats": filter_non_scalars(to_host(stats)),
        }
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)

    # ------------------------------------------------------------------
    # the learn loop
    # ------------------------------------------------------------------

    def learn(self) -> Dict[str, Any]:
        """Epochs → batches → n updates per batch, with interval checkpoints,
        interval eval, and best-reward checkpointing (reference
        ``accelerate_base_trainer.py:433-553``).

        Resilience wiring (docs/RESILIENCE.md): SIGTERM/SIGINT handlers are
        installed for the duration of the loop (emergency checkpoint at the
        next step boundary, then :class:`TrainingPreempted`); any exception
        — including a crash — flushes the tracker and exports the span
        trace before propagating, so a dying run keeps its metrics."""
        set_global_mesh(self.mesh)
        logger.info("Starting training")
        self.prepare_learning()
        self._note_state_bytes()
        self.maybe_resume()
        self._maybe_start_serving()
        try:
            # step_host holds the open `learn/step_host` span between two
            # train steps; every way out of the loop closes it
            with self.resilience.preemption, ExitStack() as step_host:
                return self._learn_loop(step_host)
        except BaseException as e:
            # crash-safe shutdown: without this, an exception loses every
            # buffered tracker record and the whole Perfetto trace — and
            # the flight recorder's last-moments ring (flightrec.json)
            self._shutdown_observability(
                reason=f"{type(e).__name__}: {e}"
            )
            raise
        finally:
            # async actors (threads or a remote fleet waiting on the weight
            # channel) must not outlive the learn loop — on a clean finish
            # AND on every crash/preemption path (docs/ASYNC_RL.md)
            self._shutdown_collectors()

    def _maybe_start_serving(self) -> None:
        """Stand up the serving frontend (``serve.enabled``,
        docs/SERVING.md): a dedicated ContinuousEngine built through the
        SAME slot-refill program cache as the collection engines, owned by
        the serve pump thread for the whole ``learn()`` run, receiving
        every published params version at step boundaries."""
        cfg = self.config.serve
        if not cfg.enabled or self._serve is not None:
            return
        if not hasattr(self, "_cb_make_engine"):
            raise ValueError(
                f"serve.enabled: {type(self).__name__} has no continuous-"
                "batching engine path to serve from (PPO-family trainers "
                "only)"
            )
        gen_kwargs: Dict[str, Any] = {}
        if int(cfg.max_new_tokens) > 0:
            gen_kwargs["max_new_tokens"] = int(cfg.max_new_tokens)
        gen_config, extra_kwargs = self._resolve_gen_config(
            eval_mode=True, **gen_kwargs
        )
        engine = self._cb_make_engine(
            gen_config,
            extra_kwargs,
            int(cfg.slots),
            1,
            tag="serve",
            version=self.iter_count,
        )
        engine.reserve_slots = int(cfg.reserve_slots)
        for tenant, blocks in (cfg.tenant_quota_blocks or {}).items():
            engine.allocator.set_tenant_quota(str(tenant), int(blocks))
        if int(cfg.host_tier_blocks) > 0:
            from trlx_tpu.ops.paged_kv import block_bytes
            from trlx_tpu.serve.tiering import HostTier

            engine.attach_host_tier(
                HostTier(
                    int(cfg.host_tier_blocks),
                    block_bytes=block_bytes(engine.state.cache),
                )
            )
        from trlx_tpu.serve.server import ServeServer

        slo_s = {
            k: float(v)
            for k, v in (
                ("interactive", cfg.slo_interactive_s),
                ("eval", cfg.slo_eval_s),
                ("actor", cfg.slo_actor_s),
            )
            if float(v) > 0
        }
        self._serve = ServeServer(
            engine,
            default_tenant=cfg.default_tenant,
            default_class=cfg.default_class,
            slo_s=slo_s,
            max_queue=int(cfg.max_queue),
            stream_buffer=int(cfg.stream_buffer),
            drain_timeout_s=float(cfg.drain_timeout_s),
            retain_param_versions=int(cfg.retain_param_versions),
            default_max_new_tokens=int(cfg.max_new_tokens),
        )
        # publish BEFORE exposing the HTTP port: the pump drains params
        # ahead of ingress, so every request admitted once the listener is
        # up is stamped with a real version (never a pre-publish None)
        self._serve.publish(self._serve_params_copy(), version=self.iter_count)
        self._serve.start(host=cfg.host, port=int(cfg.port))
        logger.info(
            f"serving frontend up on {cfg.host}:{self._serve.port} "
            f"({cfg.slots} slots, classes {list(slo_s) or 'un-SLO-gated'})"
        )

    def _serve_params_copy(self) -> Any:
        """Buffer-owning copy of the engine-params tree for the serve pump
        (the weight-channel idiom, ``async_rl/channel.py``): the train step
        donates its input state, so a published alias of ``state.params``
        would be invalidated under the pump mid-decode — and under
        ``serve.retain_param_versions`` the history must stay readable
        after arbitrarily many later updates."""
        return jax.tree_util.tree_map(jnp.copy, self._engine_params())

    def _shutdown_collectors(self) -> None:
        """Stop any background experience collectors (PPO's async
        actor/learner split overrides and chains back here). Never raises.

        Closing the prompt-iterator generator chain unwinds
        ``PrefetchLoader.__iter__``'s ``finally`` — which is what joins the
        ``trlx-prefetch`` worker: a consumer that stopped mid-epoch
        otherwise leaves the worker parked on a full queue until the
        trainer is garbage-collected (caught by the leaked-thread sentinel
        in tests/conftest.py, the dynamic complement of graftlint GL403).

        The serving frontend drains FIRST (new admissions 503, in-flight
        requests get ``serve.drain_timeout_s`` to finish, both serve
        threads joined) — on the clean path AND on every crash/preemption
        path, composing with the emergency-checkpoint exit: a SIGTERM'd
        run writes its checkpoint at the step boundary, then drains serving
        on the way out (docs/SERVING.md "Graceful drain")."""
        serve = self._serve
        if serve is not None:
            self._serve = None
            try:
                serve.drain()
            except Exception:  # pragma: no cover - defensive teardown
                logger.warning("serve drain failed", exc_info=True)
        self._close_prompt_iterator()

    def _close_prompt_iterator(self) -> None:
        iterator = getattr(self, "prompt_iterator", None)
        if iterator is not None and hasattr(iterator, "close"):
            try:
                iterator.close()
            except Exception:  # pragma: no cover - defensive
                pass

    def _shutdown_observability(self, reason: Optional[str] = None) -> None:
        """Best-effort flush of profiler, span trace, and tracker — callable
        from exception paths, never raising. A non-None ``reason`` marks a
        crash path and additionally dumps the flight recorder
        (``flightrec.json``): any exception, NaN-halt, and preemption all
        funnel through here (docs/OBSERVABILITY.md "Flight recorder")."""
        try:
            self.obs.profile.stop()
        except Exception:  # pragma: no cover - defensive
            pass
        if reason is not None:
            try:
                self.obs.dump_flight_record(reason=reason)
            except Exception:  # pragma: no cover - defensive
                pass
        self._export_observability()
        try:
            self.tracker.finish()
        except Exception:  # pragma: no cover - defensive
            pass

    def _triage_extra(self, arrays: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """Subclass hook: derived per-token quantities worth keeping with a
        triaged batch (e.g. the PPO trainer adds advantages/returns and
        per-token logprob deltas). Must not raise past its own best effort."""
        return {}

    def _warm_triage(self, batch: Any) -> None:
        """Subclass hook, called once at the first optimizer step: a trainer
        whose every run reaches :meth:`_dump_triage` builds the programs of
        its :meth:`_triage_extra` here, in set-up, not at the trip."""

    def _triage_rows(self, batch: Any) -> Dict[str, np.ndarray]:
        """The first ``TRIAGE_MAX_ROWS`` rows of every array of a batch."""
        if hasattr(batch, "_asdict"):
            batch = batch._asdict()
        if not isinstance(batch, dict):
            return {}
        return {
            key: np.asarray(value[:TRIAGE_MAX_ROWS])
            for key, value in batch.items()
            if hasattr(value, "shape") and getattr(value, "ndim", 0) > 0
        }

    def _dump_triage(self, reason: str, stats: Dict[str, Any]) -> Optional[str]:
        """Write the batch of the step being landed as ``triage/step<N>.npz`` so a
        bad update is reproducible offline — tokens, masks, and whatever the
        trainer derives (docs/OBSERVABILITY.md "Training dynamics").

        Bounded (first ``TRIAGE_MAX_ROWS`` rows, at most ``TRIAGE_MAX_DUMPS``
        files per run), atomic (tmp + ``os.replace``), process 0 only, and
        never raises — it runs on failure paths. Returns the path or None."""
        if jax.process_index() != 0:
            return None
        directory = self.obs._trace_dir
        if not directory or self._triage_dumps >= TRIAGE_MAX_DUMPS:
            return None
        try:
            arrays = self._triage_rows(self._landing_batch)
            if not arrays:
                return None
            try:
                extra = self._triage_extra(arrays)
            except Exception:  # pragma: no cover - defensive
                extra = {}
            for key, value in extra.items():
                arrays.setdefault(key, np.asarray(value)[:TRIAGE_MAX_ROWS])
            meta = {
                "step": self.iter_count,
                "reason": reason,
                "rows": int(next(iter(arrays.values())).shape[0]),
                "stats": {
                    k: float(v)
                    for k, v in stats.items()
                    if isinstance(v, (int, float)) and np.isfinite(v)
                },
            }
            arrays["__meta__"] = np.frombuffer(
                json.dumps(meta).encode("utf-8"), dtype=np.uint8
            )
            triage_dir = os.path.join(directory, "triage")
            os.makedirs(triage_dir, exist_ok=True)
            path = os.path.join(triage_dir, f"step{self.iter_count}.npz")
            tmp = path + ".tmp.npz"
            with open(tmp, "wb") as f:
                np.savez(f, **arrays)
            os.replace(tmp, path)
            self._triage_dumps += 1
            self.obs.metrics.inc("health/triage_dumps")
            self.obs.flightrec.record(
                "triage",
                {
                    "step": self.iter_count,
                    "reason": reason,
                    "path": path,
                    "keys": sorted(k for k in arrays if k != "__meta__"),
                },
            )
            logger.warning(f"triage batch dumped to {path} ({reason})")
            return path
        except Exception:  # pragma: no cover - defensive, crash-path code
            logger.warning("triage dump failed", exc_info=True)
            return None

    def _check_faults_and_preemption(
        self, step: Optional[int] = None, land: Callable[[], None] = lambda: None
    ) -> None:
        """Step-boundary seam, called before every update is launched: deliver
        any fault-plan signals for update ``step``, coordinate the preemption
        flag across processes, then honor an agreed request with one committed
        emergency checkpoint. ``step`` is ``iter_count``, or one more while the
        previous update is still in flight; ``land`` lands that one, and is
        called before a request is honored: the checkpoint is of a landed
        state with nothing in flight (a step the fault plan names is launched
        with nothing in flight to begin with)."""
        import signal as _signal

        if step is None:
            step = step
        plan = self.resilience.plan
        if plan:
            # raise_signal runs the installed handler synchronously, so the
            # request is honored at THIS boundary — fully deterministic
            if plan.poll("sigterm", step=step):
                _signal.raise_signal(_signal.SIGTERM)
            if plan.poll("sigint", step=step):
                _signal.raise_signal(_signal.SIGINT)
            # the multihost fault: every process polls (lockstep counters),
            # only process 0 is actually signaled — the coordination
            # allgather below must carry the request to the peers
            if (
                plan.poll("sigterm_one_proc", step=step)
                and jax.process_index() == 0
            ):
                _signal.raise_signal(_signal.SIGTERM)
            if plan.poll("flightrec_dump", step=step):
                # deterministic flight-recorder exercise: same dump path as
                # the crash/NaN-halt/preemption shutdown, no crash needed
                self.obs.dump_flight_record(
                    reason=f"fault plan: flightrec_dump@step:{step}"
                )
            if plan.poll("health_trip", step=step):
                # arm an injected detector trip; this step's health update
                # consumes it and runs the organic flightrec+triage path
                self.obs.health.force_trip("fault_plan", step=step)
            if self._serve is not None and plan.poll(
                "request_flood", step=step
            ):
                # admission-control drill (docs/RESILIENCE.md): a synthetic
                # burst through the real gate must shed load with 429s
                rejected = self._serve.flood_drill()
                logger.warning(
                    f"request_flood drill at step {step}: "
                    f"{rejected} synthetic requests shed by admission"
                )
        if self._serve is not None:
            # serve-while-training: every step boundary publishes the fresh
            # params; the pump adopts them at its next serve-idle point, so
            # every response is generated under ONE params version
            self._serve.publish(self._serve_params_copy(), version=step)
        preemption = self.resilience.preemption
        requested = preemption.requested
        coordinate = self.resilience.config.coordinate_preemption
        if self.obs.cluster.enabled or coordinate:
            # cross-rank telemetry beat (docs/OBSERVABILITY.md "Distributed
            # telemetry"): ONE allgather carries the preemption flag AND the
            # per-rank scalars (step time, host wait, tokens/s, memory) —
            # the coordinated-preemption collective, not a new sync point.
            # With coordination disabled the beat stays local (no
            # collective) and only this rank's gauges publish. The beat is
            # the ONLY collective on this boundary and whether it posts
            # depends only on `coordinate` (rank-uniform config, graftlint
            # GL704) — never on the per-process TRLX_TPU_CLUSTER_TELEMETRY
            # env gate, which would let one mis-launched rank post a
            # mismatched collective and hang the pod (a telemetry-disabled
            # rank still rides the same allgather, skipping only the
            # analysis).
            requested_any = self.obs.cluster.beat(
                requested, step=step, collective=coordinate
            )
            if coordinate:
                requested = requested_any
        if not requested:
            return
        land()
        if not preemption.requested:
            # this process was not signaled itself; a peer was
            preemption.request("peer preemption (coordinated)")
        self.obs.flightrec.record(
            "resilience",
            {
                "event": "preemption",
                "signal": preemption.signal_received,
                "step": self.iter_count,
            },
        )
        subfolder = f"checkpoint_{self.iter_count:0{len(str(self.total_steps))}d}"
        path = os.path.join(self.config.train.checkpoint_dir, subfolder)
        logger.warning(
            f"preemption ({preemption.signal_received}): writing emergency "
            f"checkpoint to {path}"
        )
        self.save(path, emergency=True)
        wait_for_saves()  # the commit marker must land before we exit
        raise TrainingPreempted(
            f"preempted by {preemption.signal_received}; emergency checkpoint "
            f"committed at {path} — relaunch with "
            "train.resume_from_checkpoint to continue",
            checkpoint_dir=path,
        )

    def _spanned(self, iterable, name: str, **args):
        """``iterable`` with every ``next()`` inside a span (the learn
        loop's collation, or its wait on the prefetch thread)."""
        it = iter(iterable)
        while True:
            with self.obs.span(name, **args):
                try:
                    item = next(it)
                except StopIteration:
                    return
            yield item

    def post_backward_touches_state(self, updates: int) -> bool:
        """Whether :meth:`post_backward_callback`, run once ``updates``
        updates have landed, reads or replaces ``self.state`` (ILQL's target
        sync). The learn loop then launches nothing ahead of it; a callback
        that stays on the host (PPO's KL controller) runs while the next
        step is on the chip."""
        return False

    def _step_plan(self, skip_target: int):
        """The learn loop's steps in the order they run, ``(batch,
        last_replay)`` each, and ``None`` after the last step of an epoch
        that ran one. Nothing here touches the device: the loop looks one
        item ahead to know whether a step is the last before a boundary.

        Emergency resume: the checkpoint froze the run between two updates.
        The first ``skip_target`` slots are passed over — no device work, no
        eval, no callbacks (all of that happened before the checkpoint; the
        rollout RNG and controller state were restored with it), so the
        resumed run's stream of device calls is an uninterrupted run's."""
        done = 0
        for _ in range(self.config.train.epochs):
            if done < skip_target:
                # fully-skipped epochs cost nothing (not even collation)
                try:
                    per_epoch = len(self.train_dataloader) * self.n_updates_per_batch
                except TypeError:
                    per_epoch = None
                if per_epoch and done + per_epoch <= skip_target:
                    done += per_epoch
                    # trainers that reuse one loader across epochs (SFT/
                    # ILQL) draw a fresh shuffle per epoch from a stateful
                    # RNG: burn the skipped epoch's draw so the resume
                    # epoch's order matches the uninterrupted run. Trainers
                    # that rebuild the loader every epoch (PPO's post-epoch
                    # refill) must NOT burn — their resumed loader is
                    # already the fresh one.
                    if not getattr(self, "_fresh_loader_per_epoch", False) and hasattr(
                        self.train_dataloader, "advance_epoch"
                    ):
                        self.train_dataloader.advance_epoch()
                    continue
            epoch_ran = False
            for batch in self._spanned(
                self._maybe_prefetch(self.train_dataloader), "learn/loader", stage="collate"
            ):
                for replay in range(self.n_updates_per_batch):
                    if done < skip_target:
                        done += 1
                        continue
                    epoch_ran = True
                    yield batch, replay == self.n_updates_per_batch - 1
            if epoch_ran:
                yield None

    def _learn_loop(self, step_host: ExitStack) -> Dict[str, Any]:
        """The learner runs one step ahead of its records: a step is a
        *launch* (:meth:`_launch`: place or reuse the batch, enqueue the
        program) and a *landing* (:meth:`_land`: fence, record, checkpoint,
        eval, tracker), and step N+1 is launched BEFORE step N lands whenever
        it is known and no boundary lies between the two
        (:meth:`_boundary_after`), so the chip is inside N+1 while the host
        writes N's record. Depth one: at most one step in flight beside the
        one being landed. The device calls, their order and their arguments
        are those of a loop that lands every step before the next launch."""
        emergency_resume = self._emergency_resume
        self._emergency_resume = False
        skip_target = self.iter_count if emergency_resume else 0

        if emergency_resume:
            results: Dict[str, Any] = {}
            logger.info(
                f"emergency resume: fast-forwarding to update {skip_target}"
            )
        else:
            with self.obs.span("setup/first_eval") as eval_sp:
                results = self.evaluate()
            self.obs.setup.first_eval_s = eval_sp.duration
            self.obs.setup.first_eval_end = eval_sp.t1
            self.tracker.log(results, step=self.iter_count)
            self._report_sweep(results)
        if self._host_gap_t0 is None:  # no collection came before the loop
            self._host_gap_t0 = perf_counter()
        if self._step_mark is None:
            self._step_mark = tracing.mark()

        loop = _LearnLoop(
            step_host=step_host,
            clock=Clock(),
            tbar=logging.tqdm(
                initial=self.iter_count,
                total=self.total_steps,
                disable=jax.process_index() != 0,
                position=0,
                leave=True,
            ),
            results=results,
        )
        # closed on every way out: a loop left mid-epoch leaves no prefetch
        # worker behind
        with closing(_Lookahead(self._step_plan(skip_target))) as plan:
            self._run_steps(loop, plan)
        if not loop.finished:  # the epochs ran out before total_steps
            self.obs.profile.stop()
            loop.tbar.close()
            wait_for_saves()  # async saves must land before exit
            self._export_observability()
            self.tracker.finish()
        return loop.results

    def _run_steps(self, loop: "_LearnLoop", plan: "_Lookahead") -> None:
        """Launch and land the plan's steps, until it ends or a landing
        reaches ``total_steps`` (``loop.finished``)."""
        flying: Optional[_Flight] = None  # launched and not landed

        def land_flying() -> None:
            nonlocal flying
            if flying is not None:
                landing, flying = flying, None
                self._land(loop, landing)

        for planned in plan:
            if planned is None:  # an epoch's end: its last step has landed
                self._drop_batch_memo()  # free the batch's HBM before rollouts
                loop.step_host.close()
                with self.obs.span("learn/post_epoch"):
                    self.post_epoch_callback()
                continue
            batch, last_replay = planned
            step = self.iter_count + (flying is not None)
            self._check_faults_and_preemption(step, land=land_flying)
            launched = self._launch(loop, batch, last_replay, step, ahead=flying is not None)
            if flying is not None:  # it lands while the chip runs the step after it
                landing, flying = flying, None
                self._land(loop, landing, ahead_of=launched)
                if launched.discarded:
                    # a rollback took away the state it was computed from:
                    # the same update again, from the restored one
                    launched = self._launch(loop, batch, last_replay, step, ahead=False)
            if self._boundary_after(launched, plan.peek()):
                self._land(loop, launched)
                if loop.finished:
                    return
            else:
                flying = launched

    def _boundary_after(self, flight: "_Flight", nxt: Any) -> bool:
        """Whether ``flight``, just launched, lands before anything else is
        launched: the next step is not known (``nxt`` is None: an epoch's
        end, with its collection, or the plan's), or something between the
        two acts on the landed state — a checkpoint, an evaluation or the
        run's end falls due, the profiler's window opens or closes, the
        fault plan names the next step, the batch's callback touches the
        state — or this is the job's first step (its compile, the triage
        programs, the flops thread). Step counts and configuration alone
        decide, so every process decides alike and collective beats stay
        aligned."""
        if nxt is None or flight.first_of_job:
            return True
        landed = flight.step + 1  # iter_count once it lands: the next step's index
        train = self.config.train
        if (
            landed % train.checkpoint_interval == 0
            or landed % train.eval_interval == 0
            or landed >= self.total_steps
        ):
            return True
        profile = self.obs.profile
        if profile.stops_at(flight.step) or profile.starts_at(landed):
            return True
        if self.resilience.plan.due(landed):
            return True
        return flight.last_replay and self.post_backward_touches_state(landed)

    def _launch(self, loop: "_LearnLoop", batch: Any, last_replay: bool, step: int,
                ahead: bool) -> "_Flight":
        """Update ``step`` goes to the chip; its landing follows, after the
        next step's launch where that one may run ahead."""
        self.obs.profile.on_step_start(step)
        loop.step_host.close()  # the host gap ends where the step begins
        first_of_job = self._train_step_fn is None
        with self.obs.profile.step_annotation("train", step):
            with self.obs.span("train_step") as sp:
                device_stats = self.train_step(batch, step=step)
        # what the host does between a launch and a landing, and from a
        # landing to the next launch or to the post-epoch collection
        loop.step_host.enter_context(self.obs.span("learn/step_host"))
        return _Flight(
            step=step, batch=batch, stats=device_stats, t_open=sp.t0, dispatch=sp.duration,
            ahead=ahead, last_replay=last_replay, first_of_job=first_of_job,
        )

    def _discard(self, flight: "_Flight") -> None:
        """A rollback lands on the step before ``flight``: what ``flight``
        computed came from the rejected state and goes with it. It is no
        update: ``iter_count`` does not advance and the loop launches the
        same update again, from the restored state."""
        stats = filter_non_scalars(to_host(flight.stats))
        stats["learn/ahead"] = 1.0
        stats["learn/discarded"] = 1.0
        flight.discarded = True
        self.obs.flightrec.record(
            "resilience", {"event": "discarded_in_flight", "step": flight.step}
        )
        self.tracker.log(stats, step=self.iter_count)

    def _land(self, loop: "_LearnLoop", flight: "_Flight",  # noqa: C901
              ahead_of: Optional["_Flight"] = None) -> None:
        """Fence update ``flight.step`` and write its record: everything
        from the stats' way to the host to ``tracker.log``, with the
        checkpoint and the evaluation that fall due. ``ahead_of`` is the step
        launched after it, in flight meanwhile: the fence is then on the stat
        outputs alone (the state was donated to ``ahead_of``; one program's
        outputs become ready together), and a verdict that acts on the state
        (a rejected update, a health trip's dump) first waits for it."""
        loop.step_host.close()
        self._landing_batch = batch = flight.batch
        # the step launched ahead, to wait for before a verdict acts on the
        # state (None, an empty tree, where nothing is in flight)
        in_flight = (self.state, ahead_of.stats) if ahead_of is not None else None
        with self.obs.span("learn/land", step=flight.step) as sp:
            # a boundary fences the new state AND the stat outputs: the
            # donated-state update can still be in flight after the stats
            # land, and without any fence the timer reads async dispatch
            # latency
            t_ready = sp.block(
                flight.stats if ahead_of is not None else (self.state, flight.stats)
            )
            # the interval this record tiles the learn phase with: from the
            # previous fence (or the end of the collection) to this one
            t_prev, self._host_gap_t0 = self._host_gap_t0, t_ready
            # what the runtime, the collector and the scheduler did in it
            step_mark = tracing.mark()
            attributed = attributed_between(self._step_mark, step_mark)
            self._step_mark = step_mark
            host_stats = to_host(flight.stats)
            stats = filter_non_scalars(host_stats)
            # collapse the on-device distribution sketches into
            # dist/* percentile gauges BEFORE the filter's output is
            # used — the raw histogram arrays live only in host_stats
            stats.update(self.obs.dynamics.summarize(host_stats))
            # a guard-rejected update is the one moment the offending
            # batch is still in hand — triage it before any rollback
            # (docs/RESILIENCE.md "Update guard", OBSERVABILITY.md
            # "Training dynamics")
            if stats.get(UPDATE_OK_KEY) == 0.0:
                jax.block_until_ready(in_flight)
                if self._dump_triage("update_guard", stats):
                    self.obs.dump_flight_record(
                        reason=f"update guard rejected step {self.iter_count}"
                    )
            # update guard: the on-device finiteness flag landed
            # with the stats; skip was already applied on device,
            # rollback/halt are host decisions (docs/RESILIENCE.md)
            if self.resilience.guard.after_step(stats) == "rollback":
                if ahead_of is not None:
                    self._discard(ahead_of)
                self._rollback_to_committed()
            # never shorter than the chip's own time on the step: it opens
            # at the launch, or where the previous step's fence returned if
            # the launch came before that
            step_time = t_ready - max(flight.t_open, t_prev)
            stats["time/step"] = step_time
            stats["time/train_step"] = step_time
            # host time between the previous fence (or the end of the
            # collection) and this step's launch, 0 for a step launched
            # ahead: with time/train_step it tiles the learn phase
            stats["time/step_gap"] = max(flight.t_open - t_prev, 0.0)
            stats["time/train_step_dispatch"] = flight.dispatch
            stats["time/train_step_wait"] = sp.wait
            stats["learn/ahead"] = float(flight.ahead)
            # of the previous landing, the seconds after its fence that
            # passed while this step was on the chip
            stats["time/step_host_hidden"] = flight.hidden
            stats.update(attributed)
            real_tokens, fed_tokens, width = self._batch_token_counts(batch)
            stats["learn/pad_frac"] = (
                1.0 - real_tokens / fed_tokens if fed_tokens else 0.0
            )
            stats["learn/step_width"] = float(width)
            stats["learn/grad_param_frac"] = self._grad_param_frac
            self._note_step(stats, attributed, (width, flight.ahead), t_ready,
                            next_launch=ahead_of.dispatch if ahead_of is not None else 0.0)
            (
                stats["learn/attn_visited_frac"],
                stats["learn/attn_tile"],
                stats["learn/attn_interior_frac"],
            ) = self._attn_tile_walk(width)
            if getattr(self.tcfg, "index_topk", 0):  # every layer attends under the selection
                stats["learn/attn_selected_frac"] = selected_frac(width, self.tcfg.index_topk)
            if getattr(self.tcfg, "sparse_topk", 0):  # the attention layers' pairs under the block selection
                chosen, causal = block_selected_pairs(width, self.tcfg)
                stats["learn/attn_block_selected_frac"] = chosen / max(causal, 1.0)
            batch_size = next(
                v.shape[0] for v in batch.values() if hasattr(v, "shape")
            ) if isinstance(batch, dict) else self.config.train.batch_size
            stats.update(
                self.obs.throughput.step_stats(
                    step_time,
                    tokens=real_tokens,
                    samples=batch_size,
                    flops_per_device=self._ensure_train_step_flops(
                        self._last_batch_sharded
                    ),
                )
            )
            stats.update(self.obs.memory.collect(self.programs.account()))
            # feed the NEXT boundary's cluster beat (distributed
            # telemetry) with this step's scalars, and surface the
            # tracer's drop counter before the snapshot below
            self.obs.cluster.note_step(
                step_time,
                tokens_per_sec=stats.get(
                    "throughput/tokens_per_sec", 0.0
                ),
                device_bytes=stats.get(
                    "memory/device_bytes_in_use",
                    stats.get("memory/host_rss_bytes", 0.0),
                ),
            )
            # elastic fleet membership rides the same beat vector
            # (async_rl.transport: collective; None off-fleet)
            collector = getattr(self, "_async", None)
            if collector is not None and hasattr(
                collector, "fleet_size"
            ):
                self.obs.cluster.note_fleet(collector.fleet_size())
            self.obs.note_dropped_spans()
            stats.update(self.obs.metrics.snapshot())
            if self._serve is not None:
                # per-tenant/per-class SLO percentiles live on the
                # HTTP /metrics endpoint; the flat SERVE_KEYS
                # gauges ride the training metric stream
                stats.update(self._serve.flat_metrics())
            # windowed health detectors over this step's metric
            # stream; a trip transition dumps the flight record and
            # triages the batch that produced it
            stats.update(
                self.obs.health.update(stats, step=self.iter_count)
            )
            tripped = self.obs.health.just_tripped
            if tripped is not None:
                jax.block_until_ready(in_flight)
                if self._dump_triage(f"health:{tripped}", stats):
                    # this step's registry snapshot is already taken;
                    # surface the counter on the step that dumped
                    stats["health/triage_dumps"] = float(
                        self._triage_dumps
                    )
                self.obs.dump_flight_record(
                    reason=f"health_trip: {tripped} @ step {self.iter_count}"
                )
            # the flight recorder keeps the last N steps' stats for
            # the crash dump (docs/OBSERVABILITY.md)
            self.obs.flightrec.record(
                "step", {"iter": self.iter_count, "stats": stats}
            )
            loop.clock.tick(batch_size)
            stats["time/per_1k_samples"] = loop.clock.get_stat(1000)
            self.obs.profile.on_step_end(self.iter_count)
            self.iter_count += 1
        # checkpoint, evaluation and the tracker's write: between spans again
        loop.step_host.enter_context(self.obs.span("learn/step_host"))

        if self.iter_count % self.config.train.checkpoint_interval == 0:
            # retention ring: prune BEFORE saving so the join
            # inside prune waits on the long-finished previous
            # save, not the one about to dispatch
            keep = self.resilience.config.keep_last_n
            if keep > 0:
                prune_checkpoints(self.config.train.checkpoint_dir, keep)
            subfolder = f"checkpoint_{self.iter_count:0{len(str(self.total_steps))}d}"
            self.save(os.path.join(self.config.train.checkpoint_dir, subfolder))

        if self.iter_count % self.config.train.eval_interval == 0:
            loop.results = self.evaluate()
            stats.update(loop.results)
            self._report_sweep(stats)
            if self.config.train.save_best:
                reward = stats.get(
                    "reward/mean", stats.get("metrics/reward", -float("inf"))
                )
                if reward > self.best_reward:
                    self.best_reward = reward
                    best_path = os.path.join(
                        self.config.train.checkpoint_dir, "best_checkpoint"
                    )
                    logger.info(f"Saving best state so far into {best_path}")
                    self.save(best_path)

        desc = " | ".join(
            f"{k}: {significant(v)}"
            for k, v in stats.items()
            if k.startswith("losses/")
        )
        loop.tbar.set_description(f"[{desc}]")
        loop.tbar.update()

        if self.iter_count >= self.total_steps:
            self.obs.profile.stop()
            # the flops analysis runs on a daemon thread; join it
            # here so even a run too short for it to land mid-loop
            # still reports a final measured MFU
            flops = self._ensure_train_step_flops(
                self._last_batch_sharded, wait=True
            )
            if flops and "throughput/mfu" not in stats:
                stats["throughput/mfu"] = obs_mfu(
                    flops, step_time, self.obs.throughput.peak
                )
            self._drop_batch_memo()
            loop.results = self.evaluate()
            stats.update(loop.results)
            stats.update(self.obs.throughput.summary())
            self.tracker.log(stats, step=self.iter_count)
            self._report_sweep(stats)
            subfolder = f"checkpoint_{self.iter_count:0{len(str(self.total_steps))}d}"
            self.save(os.path.join(self.config.train.checkpoint_dir, subfolder))
            loop.tbar.close()
            wait_for_saves()  # async saves must land before exit
            self._export_observability()
            # flush/close the tracker (W&B runs must finalize;
            # JSONL transparently reopens if logged again)
            self.tracker.finish()
            loop.finished = True
            return

        self.tracker.log(stats, step=self.iter_count)
        if flight.last_replay:
            self.post_backward_callback()
        if ahead_of is not None and not _is_ready(ahead_of.stats):
            ahead_of.hidden = perf_counter() - t_ready

    # ------------------------------------------------------------------
    # slow intervals (docs/OBSERVABILITY.md "A slow interval names its cause")
    # ------------------------------------------------------------------

    def _note_interval(self, kind: str, key: Any, label: str, seconds: float,
                       parts: Dict[str, float], t0: float, t1: float) -> None:
        """One more interval of ``kind`` (``cycle``; ``step``, compared at
        equal width ``key``). One that ran long is logged once with its
        attribution, counted, and kept in the flight recorder's ring."""
        history = self._intervals.setdefault((kind, key), deque(maxlen=SLOW_HISTORY))
        # wall less wait less CPU: the thread was runnable and not running,
        # or blocked in a call that is not the fence
        waited = sum(parts.get(k, 0.0) for k in _WAIT_PARTS)
        parts = {**parts, "off cpu": max(seconds - waited - parts["host/cpu_s"], 0.0)}
        median = statistics.median(h[0] for h in history) if history else 0.0
        if len(history) >= SLOW_MIN_HISTORY and seconds > SLOW_INTERVAL_RATIO * median:
            retraced = parts["runtime/retrace_s"] + parts["runtime/compile_s"] > 0
            line, verdict = slow_interval_line(
                label, seconds, median, parts, list(history),
                tracing.recent_programs(t0, t1) if retraced else [])
            logger.warning(line)
            self.obs.metrics.inc(f"host/slow_{kind}s")
            self.obs.flightrec.record(
                "slow_interval", {"kind": kind, "verdict": verdict, "line": line, **parts})
        history.append((seconds, parts))

    def _note_step(self, stats: Dict[str, float], attributed: Dict[str, float], key: Any,
                   t_fence: float, next_launch: float = 0.0) -> None:
        """A step record's interval: previous fence to this one. ``key`` is
        what makes two steps alike: the width, and whether the step was
        launched ahead (its launch then lies before the interval, and the
        landing of the step before it inside). ``next_launch``: seconds in
        the launch of the step after it, where that fell inside the interval
        (a shape's first step loads or compiles its program there)."""
        seconds = stats["time/step_gap"] + stats["time/train_step"]
        parts = {
            "train_step wait": stats["time/train_step_wait"],
            "train_step dispatch": stats["time/train_step_dispatch"],
            "next launch": next_launch,
            "step gap": stats["time/step_gap"],
            **attributed,
        }
        self._note_interval("step", key, f"step {self.iter_count}", seconds, parts,
                            t_fence - seconds, t_fence)
        if self._cycle is not None:
            self._cycle["seconds"] += seconds
            for k, v in parts.items():
                self._cycle["parts"][k] = self._cycle["parts"].get(k, 0.0) + v

    def _open_cycle(self, record: Dict[str, float], attributed: Dict[str, float],
                    t0: float) -> None:
        """A collection's record opens its cycle's account; the steps add to
        it and the next collection closes it."""
        self._cycle = {
            "n": self.obs.tracer.cycle, "t0": t0, "seconds": record["time/exp"],
            "parts": {
                "generate wait": record.get("time/generate_wait", 0.0),
                "generate dispatch": record.get("time/generate_dispatch", 0.0),
                "score wait": record.get("time/score", 0.0),
                "reward": record.get("time/reward", 0.0),
                "collect host": record.get("time/collect_host", 0.0),
                **attributed,
            },
        }

    def _close_cycle(self) -> None:
        self.obs.memory.log_account()
        cycle, self._cycle = self._cycle, None
        if cycle is not None and "step gap" in cycle["parts"]:
            self._note_interval("cycle", None, f"cycle {cycle['n']}", cycle["seconds"],
                                cycle["parts"], cycle["t0"], cycle["t0"] + cycle["seconds"])

    # ------------------------------------------------------------------
    # checkpointing
    # ------------------------------------------------------------------

    def maybe_resume(self) -> None:
        """Restore the newest interval checkpoint when
        ``train.resume_from_checkpoint`` is set — relaunching a crashed or
        preempted run picks up where it left off (reference: Ray session
        restore ``accelerate_base_trainer.py:452-460``; NeMo
        ``resume_if_exists``).

        Idempotent; ``train()`` invokes it *before* the initial PPO rollout
        collection (rollout behavior-logprobs must come from the restored
        policy, not the fresh one), and ``learn()`` again as a fallback for
        direct-trainer use."""
        if getattr(self, "_resume_done", False):
            return
        self._resume_done = True
        if not getattr(self.config.train, "resume_from_checkpoint", False):
            return
        root = self.config.train.checkpoint_dir
        if not os.path.isdir(root):
            return
        wait_for_saves()  # a same-process save may still be pending its commit
        # Only COMMITTED checkpoints are candidates: a crash mid-save leaves
        # a partial dir that Orbax would die restoring — skip it with a
        # warning and take the newest committed one instead. The scan
        # (numeric step sort, commit test) is the same helper the update
        # guard's rollback uses, so resume and rollback can never disagree
        # about which checkpoint is newest.
        from trlx_tpu.utils.checkpoint import _checkpoint_step_dirs

        candidates = []
        for _step, path in _checkpoint_step_dirs(root):
            if is_committed(path):
                candidates.append(path)
            else:
                logger.warning(
                    f"skipping uncommitted/partial checkpoint {path} "
                    "(crash mid-save?); the newest committed checkpoint wins"
                )
        if not candidates:
            return
        path = candidates[-1]
        logger.info(f"Resuming training state from {path}")
        self.load(path)

    def _extra_checkpoint_state(self) -> Dict[str, Any]:
        """Host-side scalar state to persist beyond the TrainState (trainers
        override; e.g. PPO's KL controller and reward running moments —
        without them a resumed run diverges from an uninterrupted one)."""
        return {}

    def _restore_extra_checkpoint_state(self, extra: Dict[str, Any]) -> None:
        pass

    def _save_emergency_payload(self, directory: str) -> None:
        """Trainer hook: persist host-side data an exact mid-run resume
        needs beyond the TrainState (PPO: the rollout store)."""

    def _restore_emergency_payload(self, directory: str) -> None:
        pass

    @staticmethod
    def _rng_to_list(key) -> list:
        """A PRNG key as a JSON-serializable uint32 list (old-style and
        typed keys both)."""
        try:
            data = jax.random.key_data(key)
        except (TypeError, ValueError):
            data = key
        return np.asarray(jax.device_get(data), np.uint32).tolist()

    def _rng_from_list(self, data: list, template):
        arr = np.asarray(data, np.uint32)
        try:
            if jnp.issubdtype(template.dtype, jax.dtypes.prng_key):
                return jax.random.wrap_key_data(arr)
        except (AttributeError, TypeError):
            pass
        return jnp.asarray(arr)

    def save(
        self, directory: Optional[str] = None, emergency: bool = False, **kwargs
    ) -> None:
        """Checkpoint full training state (params, opt state, step, RNG).

        ``emergency=True`` (preemption path) additionally freezes the
        host-side run position — rollout RNG, eval counter, best reward,
        and the trainer's emergency payload (PPO: the rollout store) — so a
        resumed run continues bit-identically from this step boundary."""
        directory = directory or self.config.train.checkpoint_dir
        extra = {"iter_count": self.iter_count, "best_reward": self.best_reward}
        extra.update(self._extra_checkpoint_state())
        # every checkpoint records the prompt-stream position (one int):
        # interval-checkpoint resumes need the same replay as emergency
        # ones, or the fresh iterator re-draws the epoch's first prompts
        extra["prompt_chunks_drawn"] = self._prompt_chunks_drawn
        if emergency:
            extra["emergency"] = True
            extra["rollout_rng"] = self._rng_to_list(self._rollout_rng)
            extra["nth_evaluation"] = self.nth_evaluation
            if jax.process_index() == 0:
                # host-side payload files have one author; peers read them
                # back from the shared checkpoint dir on resume
                os.makedirs(directory, exist_ok=True)
                self._save_emergency_payload(directory)
        save_state(directory, self.state, extra=extra)

    def load(
        self,
        directory: Optional[str] = None,
        restore_payload: bool = True,
        **kwargs,
    ) -> None:
        directory = directory or self.config.train.checkpoint_dir
        # the one restore seam (docs/RESILIENCE.md "Elastic restore"): a
        # matching topology takes the sharded Orbax fast path unchanged; a
        # checkpoint saved on a DIFFERENT mesh (device or process count)
        # reshards host-side onto the live mesh — resilience.elastic gates
        # it, resilience/reshard_s gauges it
        from trlx_tpu.resilience.elastic import restore_state_elastic

        self.state = restore_state_elastic(
            directory,
            self.state,
            elastic=self.resilience.config.elastic,
            metrics=self.obs.metrics,
        )
        self._note_state_bytes()  # another mesh gives the leaves other shards
        extra = read_extra(directory)
        self.iter_count = int(extra.get("iter_count", 0))
        if "best_reward" in extra:
            self.best_reward = float(extra["best_reward"])
        self._restore_extra_checkpoint_state(extra)
        if restore_payload and extra.get("emergency"):
            # an emergency checkpoint froze the run mid-learn: restore the
            # host-side position so learn() fast-forwards to the boundary
            self._emergency_resume = True
            if "rollout_rng" in extra:
                self._rollout_rng = self._rng_from_list(
                    extra["rollout_rng"], self._rollout_rng
                )
            self.nth_evaluation = int(
                extra.get("nth_evaluation", self.nth_evaluation)
            )
            self._restore_emergency_payload(directory)
        if restore_payload:
            # replay the prompt-stream position: the uninterrupted run has
            # consumed `prompt_chunks_drawn` chunks by this boundary; draw
            # and discard until this run's (fresh) iterator catches up, so
            # the NEXT collection trains on the same prompts in the same
            # shuffle order. Host-only work (collation), no device cost.
            # Applies to interval checkpoints too (any save records the
            # position); rollback passes restore_payload=False — its
            # iterator is live mid-run and must not be advanced.
            target = int(extra.get("prompt_chunks_drawn", 0))
            iterator = getattr(self, "prompt_iterator", None)
            if iterator is not None and target > self._prompt_chunks_drawn:
                logger.info(
                    f"resume: fast-forwarding the prompt stream "
                    f"by {target - self._prompt_chunks_drawn} chunks"
                )
                while self._prompt_chunks_drawn < target:
                    next(iterator)

    def _rollback_to_committed(self) -> None:
        """Update-guard rollback: restore the newest committed checkpoint's
        device + controller state, keep the loop bookkeeping marching
        forward (the poison batch is skipped, not retried)."""
        root = self.config.train.checkpoint_dir
        path = newest_committed_checkpoint(root)
        if path is None:
            # rollback is flag-only on device (no keep-old select), so the
            # poisoned update has already landed — without a committed
            # checkpoint there is nothing sane to continue from
            from trlx_tpu.resilience import NonFiniteUpdateError

            raise NonFiniteUpdateError(
                f"non-finite update with update_guard='rollback' but no "
                f"committed checkpoint exists under {root} to restore — "
                "halting (lower train.checkpoint_interval, or use 'skip')"
            )
        cur_iter, cur_best = self.iter_count, self.best_reward
        self.load(path, restore_payload=False)
        self.iter_count, self.best_reward = cur_iter, cur_best
        self._drop_batch_memo()
        self.obs.flightrec.record(
            "resilience",
            {"event": "rollback", "checkpoint": path, "step": self.iter_count},
        )
        logger.warning(f"rolled back train state to {path}")

    def save_pretrained(self, directory: Optional[str] = None, **kwargs) -> None:
        directory = directory or f"{self.config.train.checkpoint_dir}/hf_model"
        save_pretrained(
            directory,
            self.state.params,
            self.tcfg,
            tokenizer_path=self.config.tokenizer.tokenizer_path,
        )

    def push_to_hub(self, repo_id: str, **kwargs) -> str:
        """Publish the current policy weights to the HF Hub (reference:
        ``modeling_base.py:30`` via ``PushToHubMixin``). Stages a full
        ``save_pretrained`` export locally, then uploads it in one call;
        see ``utils/checkpoint.py::push_to_hub`` for the offline/test
        ``uploader=`` seam."""
        from trlx_tpu.utils.checkpoint import push_to_hub

        kwargs.setdefault("tokenizer_path", self.config.tokenizer.tokenizer_path)
        return push_to_hub(repo_id, self.state.params, self.tcfg, **kwargs)
