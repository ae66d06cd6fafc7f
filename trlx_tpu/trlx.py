"""Public API: the single ``train()`` entry point.

Contract-compatible with the reference dispatcher (``trlx/trlx.py:15-123``):
a ``reward_fn`` selects online RL (PPO), ``samples`` + ``rewards`` selects
offline RL (ILQL), ``samples`` alone selects SFT. The user callback contracts
are preserved exactly:

- ``reward_fn(samples, prompts, outputs) -> List[float]``
- ``metric_fn(samples, prompts, outputs) -> Dict[str, List[float]]``
"""

import os
import time
import warnings
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.default_configs import (
    default_ilql_config,
    default_ppo_config,
    default_sft_config,
)
from trlx_tpu.utils import set_seed

_runtime_initialized = False
_T_IMPORTED = time.perf_counter()


def process_age_s() -> float:
    """Seconds since the process started: its start time from
    ``/proc/self/stat`` against the boot clock where both exist, else since
    this module was imported."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])  # field 22
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - start_ticks / os.sysconf("SC_CLK_TCK")
        if age >= 0:
            return age
    except (OSError, ValueError, IndexError, AttributeError):
        pass
    return time.perf_counter() - _T_IMPORTED


def compile_cache_dir() -> Optional[str]:
    """Where this checkout keeps JAX's persistent compile cache, or None when
    ``JAX_COMPILATION_CACHE_DIR`` places it from outside (JAX reads that
    variable itself, so nothing is set in code). The path is a function of
    the checkout alone — it is part of the cache key's directory, so a name
    that moved (temp dir, pid, time) would never hit. The program store
    (``utils/programs.py``: the job's own programs, compiled, by a key that
    needs no trace) shares the directory as ``programs/`` inside it, so
    removing it still makes a start cold."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(checkout, ".jax_cache")


def measurement_devices() -> Tuple[list, bool]:
    """``(jax.devices(), rehearsal)`` for an entry point whose output is
    read as a statement about the accelerator (``chip_smoke.py``,
    ``chipbench/run.py``). Such a run never continues on the CPU by itself: any
    platform but ``tpu`` raises — unless the caller pinned
    ``JAX_PLATFORMS=cpu``, which asks for a CPU walk of the control flow
    (``rehearsal`` is then True and the caller labels its output so)."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform == "tpu":
        return devices, False
    if platform == "cpu" and os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        return devices, True
    raise RuntimeError(
        f"JAX found platform {platform!r}, not a TPU; this entry point does "
        "not continue on another device by itself (set JAX_PLATFORMS=cpu "
        "for a CPU rehearsal)"
    )


def initialize_runtime() -> None:
    """Process-level JAX runtime setup, driven by environment variables.

    Called once at the top of :func:`train` (idempotent), and by every entry
    point that builds a trainer directly (``chip_smoke.py``, ``chipbench/run.py``),
    before the first JAX operation. Three concerns:

    - **Compile cache** — see :func:`compile_cache_dir`.
    - **Platform override** — ``TRLX_TPU_PLATFORM=cpu|tpu`` selects the JAX
      platform via ``jax.config`` before the backend initializes.
    - **Multi-host initialization** — the TPU-native equivalent of the
      reference's ``torchrun``/NCCL process-group setup (SURVEY.md §2.3
      "Distributed communication backend"). On a TPU pod, launch the same
      script on every host with ``TRLX_TPU_MULTIHOST=1`` and
      ``jax.distributed.initialize()`` auto-detects coordinator/process
      topology from the TPU metadata; elsewhere (CPU/GPU clusters, tests)
      set ``TRLX_TPU_COORDINATOR=host:port``, ``TRLX_TPU_NUM_PROCESSES``,
      and ``TRLX_TPU_PROCESS_ID`` explicitly. After initialization every
      host runs the same SPMD program over one global mesh; host-local code
      (trackers, checkpoint writes, reward fns) is already gated on
      ``jax.process_index() == 0`` throughout the trainers.

    v4 pod launch sketch::

        # on every host of a v4-32 (4 hosts × 4 chips):
        TRLX_TPU_MULTIHOST=1 python examples/ppo_sentiments.py
    """
    global _runtime_initialized
    if _runtime_initialized:
        return
    _runtime_initialized = True

    import jax

    from trlx_tpu.observability import tracing

    # before the first program is traced: set-up's account starts here
    tracing.install_sources()

    cache_dir = compile_cache_dir()
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # keep every program, however quickly it compiled: the few operations
    # set-up still runs one by one (a split of the rollout rng, a host value
    # placed) compile in a tenth of a second each, under the default floor of
    # one second, and would compile again at every start
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    platform = os.environ.get("TRLX_TPU_PLATFORM")
    if platform:
        os.environ["JAX_PLATFORMS"] = platform
        jax.config.update("jax_platforms", platform)

    coordinator = os.environ.get("TRLX_TPU_COORDINATOR")
    if os.environ.get("TRLX_TPU_MULTIHOST") or coordinator:
        requested = (platform or os.environ.get("JAX_PLATFORMS", "")).lower()
        if not requested or requested.startswith("cpu"):
            # CPU multiprocess collectives live behind an explicit backend
            # selection ("Multiprocess computations aren't implemented on
            # the CPU backend" otherwise): gloo carries the cross-process
            # allgathers/psums the multihost harness (and the
            # coordinated-preemption flag exchange) relies on. Must be set
            # before the backend initializes. The empty case covers jax's
            # automatic CPU choice (no accelerator, nothing requested) —
            # the first step-boundary preemption allgather would otherwise
            # die; when another platform wins auto-detection the setting
            # only configures the unused CPU client, so it is harmless.
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        kwargs = {}
        if coordinator:
            kwargs = dict(
                coordinator_address=coordinator,
                num_processes=int(os.environ["TRLX_TPU_NUM_PROCESSES"]),
                process_id=int(os.environ["TRLX_TPU_PROCESS_ID"]),
            )
        jax.distributed.initialize(**kwargs)


def train(  # noqa: C901
    model_path: Optional[str] = None,
    reward_fn: Optional[Callable[[List[str], List[str], List[str]], List[float]]] = None,
    dataset: Optional[Iterable[Tuple[str, float]]] = None,
    samples: Optional[List[str]] = None,
    rewards: Optional[List[float]] = None,
    prompts: Optional[List[str]] = None,
    eval_prompts: Optional[List[str]] = None,
    metric_fn: Optional[Callable[[List[str], List[str], List[str]], Dict[str, List[float]]]] = None,
    config: Optional[TRLConfig] = None,
    stop_sequences: Optional[List[str]] = None,
    init_trainer_hook: Optional[Callable] = None,
):
    """Dispatch online RL, offline RL, or supervised fine-tuning.

    Args:
        model_path: HF checkpoint path, local directory, or ``builtin:*`` spec.
        reward_fn: rates batches of generated samples; called on host with
            ``(samples, prompts, outputs)``, returns per-sample rewards.
        dataset: deprecated; use ``samples`` and ``rewards``.
        samples: offline samples — strings, or interleaved
            ``(prompt_0, output_0, prompt_1, output_1, ...)`` lists.
        rewards: per-sample scalar rewards for offline (ILQL) training.
        prompts: prompts for online rollouts.
        eval_prompts: prompts for periodic validation.
        metric_fn: computes named per-sample statistics at eval.
        config: a :class:`TRLConfig`; a method-appropriate default is used
            (with a warning) when omitted.
        stop_sequences: strings at which generations are trimmed.
        init_trainer_hook: called with the constructed trainer before any
            rollout collection or training — e.g. to transplant warm-start
            weights into the policy and its frozen KL reference (the offline
            analogue of starting from a pretrained checkpoint).
    """
    # Import for registration side effects (trainers/pipelines register here).
    import importlib

    # set-up's account (docs/OBSERVABILITY.md "Set-up"): the trainer's tracer
    # does not exist yet, so the first two spans are recorded once it does
    t_train, import_s = time.perf_counter(), process_age_s()
    initialize_runtime()
    t_runtime = time.perf_counter()
    from trlx_tpu.observability import tracing

    setup_mark, setup_programs = tracing.mark(), tracing.programs()

    for module in (
        "trlx_tpu.pipeline.offline_pipeline",
        "trlx_tpu.trainer.ppo",
        "trlx_tpu.trainer.ilql",
        "trlx_tpu.trainer.sft",
        "trlx_tpu.trainer.grpo",
        "trlx_tpu.trainer.dpo",
    ):
        importlib.import_module(module)
    from trlx_tpu.pipeline import get_pipeline
    from trlx_tpu.trainer import get_trainer

    if config is None:
        warnings.warn(
            "Passing the `config` argument implicitly is deprecated; adapt one "
            "from `trlx_tpu/data/default_configs.py` instead"
        )
        if reward_fn:
            config = default_ppo_config()
        elif rewards:
            config = default_ilql_config()
        else:
            config = default_sft_config()

    set_seed(config.train.seed)

    if dataset:
        warnings.warn("the `dataset` argument is deprecated, split it into `samples` and `rewards`")
        samples, rewards = dataset

    if model_path:
        config.model.model_path = model_path

    trainer = get_trainer(config.train.trainer)(
        config=config,
        reward_fn=reward_fn,
        metric_fn=metric_fn,
        stop_sequences=stop_sequences or [],
        **config.train.trainer_kwargs,
    )
    trainer.obs.setup.begin(t_train, import_s, setup_mark, setup_programs)
    tracer = trainer.obs.tracer
    tracer.add_complete_event("setup/runtime_init", t_train, t_runtime)
    tracer.add_complete_event("setup/build_trainer", t_runtime, time.perf_counter())
    if init_trainer_hook is not None:
        init_trainer_hook(trainer)

    batch_size = config.train.batch_size
    max_prompt_length = config.train.seq_length - config.method.gen_kwargs["max_new_tokens"]

    if reward_fn:
        # Online RL: build the prompt pipeline and collect initial experience.
        prompts = prompts or [trainer.tokenizer.bos_token] * batch_size
        if eval_prompts is None:
            eval_prompts = prompts[:batch_size]

        with trainer.obs.span("setup/pipelines", which="prompts"):
            pipeline = get_pipeline(config.train.pipeline)(
                prompts, max_prompt_length, trainer.tokenizer
            )
            trainer.add_prompt_pipeline(pipeline)
        # restore BEFORE collecting rollouts: PPO behavior logprobs must come
        # from the restored policy, not the freshly initialized one
        if hasattr(trainer, "maybe_resume"):
            trainer.maybe_resume()
        trainer.make_experience(config.method.num_rollouts)
    elif samples:
        if rewards is not None and len(samples) != len(rewards):
            raise ValueError(
                f"Number of samples {len(samples)} should match the number of rewards {len(rewards)}"
            )
        if eval_prompts is None:
            eval_prompts = [trainer.tokenizer.bos_token] * batch_size
        if rewards is not None:
            trainer.make_experience(samples, rewards, config.train.seq_length)
        else:
            trainer.make_experience(samples, config.train.seq_length)
    else:
        raise ValueError("Either `samples` or `reward_fn` should be given for training")

    with trainer.obs.span("setup/pipelines", which="eval"):
        eval_pipeline = get_pipeline(config.train.pipeline)(
            eval_prompts, max_prompt_length, trainer.tokenizer
        )
        trainer.add_eval_pipeline(eval_pipeline)

    trainer.learn()
    return trainer
