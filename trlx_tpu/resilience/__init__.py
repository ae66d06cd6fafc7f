"""Resilience: preemption-safe training, non-finite-update recovery, host-call
hardening, and deterministic fault injection.

The observability subsystem (PR 1) made runs *self-reporting*; this one makes
them *self-healing*. Four pieces, bundled per trainer as
``trainer.resilience`` (a :class:`Resilience` instance — the shape mirrors
``trainer.obs``):

- :mod:`preemption` — SIGTERM/SIGINT → emergency checkpoint at the next step
  boundary → clean exit; resume is bit-identical to an uninterrupted run;
- :mod:`guard` — on-device all-finite check fused into the train step (no
  extra host sync) with ``skip`` / ``rollback`` / ``halt`` policies;
- :mod:`retry` — retry/timeout/exponential-backoff-with-jitter around
  ``reward_fn`` and tracker publishes, with configurable fallbacks;
- :mod:`faults` — a deterministic :class:`FaultPlan`
  (``"sigterm@step:5; nan_loss@step:7"``) that tests use to prove recovery
  end-to-end on CPU.

Atomic checkpoint commits (stage → rename → marker) live in
``trlx_tpu/utils/checkpoint.py``; the guard's rollback and ``maybe_resume``
both trust only *committed* checkpoints. Knobs: ``config.resilience``
(:class:`~trlx_tpu.data.configs.ResilienceConfig`); semantics:
``docs/RESILIENCE.md``.
"""

from typing import Any, Callable, Optional

from trlx_tpu.resilience.elastic import (
    ElasticRestoreError,
    build_manifest,
    coordinate_preemption,
    manifest_mismatch,
    read_manifest,
    restore_state_elastic,
)
from trlx_tpu.resilience.faults import (
    FaultPlan,
    InjectedFault,
    get_active_plan,
    poll_fault,
    set_active_plan,
)
from trlx_tpu.resilience.guard import (
    UPDATE_OK_KEY,
    NonFiniteUpdateError,
    UpdateGuard,
)
from trlx_tpu.resilience.preemption import PreemptionHandler, TrainingPreempted
from trlx_tpu.resilience.retry import (
    HostCallGuard,
    ResilientTracker,
    neutral_rewards,
)

__all__ = [
    "ElasticRestoreError",
    "FaultPlan",
    "HostCallGuard",
    "InjectedFault",
    "NonFiniteUpdateError",
    "PreemptionHandler",
    "Resilience",
    "ResilientTracker",
    "TrainingPreempted",
    "UPDATE_OK_KEY",
    "UpdateGuard",
    "build_manifest",
    "coordinate_preemption",
    "get_active_plan",
    "manifest_mismatch",
    "neutral_rewards",
    "poll_fault",
    "read_manifest",
    "restore_state_elastic",
    "set_active_plan",
]


class Resilience:
    """Per-trainer bundle: fault plan + preemption handler + update guard +
    host-call hardening, built from ``config.resilience`` and sharing the
    trainer's metrics registry so every ``resilience/*`` counter rides the
    existing tracker stream.
    """

    def __init__(self, config: Any, metrics: Any = None):
        from trlx_tpu.data.configs import ResilienceConfig

        rcfg = getattr(config, "resilience", None)
        if rcfg is None:
            rcfg = ResilienceConfig()
        self.config = rcfg
        self.metrics = metrics
        self.plan = FaultPlan.from_config(rcfg.fault_plan)
        # low-level sites (checkpoint commit) consult the process-active
        # plan; a plan-less trainer clears it so a previous trainer's faults
        # don't leak across runs in one process
        set_active_plan(self.plan)
        self.preemption = PreemptionHandler(
            enabled=rcfg.handle_preemption,
            signals=list(rcfg.preemption_signals),
            metrics=metrics,
        )
        self.guard = UpdateGuard(
            policy=rcfg.update_guard,
            max_consecutive=rcfg.max_consecutive_nonfinite,
            metrics=metrics,
        )

    def harden_reward_fn(
        self, reward_fn: Optional[Callable], seed: int = 0
    ) -> Optional[Callable]:
        """Wrap ``reward_fn`` in retry/timeout/backoff per the config; the
        trainer installs this once so every call site (rollout scoring,
        eval) is hardened transparently."""
        if reward_fn is None:
            return None
        rcfg = self.config
        return HostCallGuard(
            reward_fn,
            name="reward",
            retries=rcfg.reward_retries,
            backoff_s=rcfg.reward_backoff_s,
            backoff_max_s=rcfg.reward_backoff_max_s,
            timeout_s=rcfg.reward_timeout_s,
            fallback=rcfg.reward_fallback,
            neutral_fn=neutral_rewards,
            max_consecutive_fallbacks=rcfg.reward_max_consecutive_fallbacks,
            metrics=self.metrics,
            plan=self.plan,
            seed=seed,
        )

    def harden_tracker(self, tracker: Any, seed: int = 0) -> Any:
        """Wrap a tracker so publish failures retry, then drop — never
        killing the run."""
        return ResilientTracker(
            tracker,
            retries=self.config.publish_retries,
            backoff_s=self.config.publish_backoff_s,
            metrics=self.metrics,
            plan=self.plan,
            seed=seed,
        )
