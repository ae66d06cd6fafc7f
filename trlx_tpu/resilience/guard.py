"""Update guard: on-device all-finite check fused into the train step, with
a host-side ``skip`` / ``rollback`` / ``halt`` policy.

A single NaN loss previously corrupted the parameters (NaN gradients flow
through ``optax.apply_updates`` into every weight) and the run kept training
on garbage until someone read the curves. The guard closes that hole with
**zero extra host syncs**:

- device side (``trainer/base.py::_build_train_step``): the step computes
  ``all_finite = isfinite(global_norm(grads))`` — the global norm is already
  computed for ``gradients/global_norm``, and any non-finite loss, grad, or
  activation NaN propagates into it. The norm is over the trained leaves
  (the step takes no gradient with respect to a leaf the mask freezes, and
  the optimizer gives such a leaf a zero update): every update is a
  function of a trained leaf's gradient, so the check covers all that can
  reach a weight. Under the ``skip`` policy it also
  selects the *old* params/opt-state via ``jnp.where`` when the check fails
  (NOTE: the select keeps both state versions live, defeating donation's
  in-place update — ≈2× train-step temp memory; ``rollback``/``halt`` are
  flag-only and keep the donated memory profile). The flag rides back in
  the stats dict the learn loop already fetches every step;
- host side (:class:`UpdateGuard`): reads ``resilience/update_ok`` from the
  landed stats and applies the configured policy:

  ``skip``      drop the poison update (device already kept the old state),
                count it, continue with the next batch;
  ``rollback``  restore the newest *committed* checkpoint from the
                retention ring (the poisoned update has landed on device —
                without a committed checkpoint this halts). Also right for
                when a bad update landed earlier, e.g. bf16 overflow
                poisoning the optimizer moments a few steps before the
                norm finally blew up;
  ``halt``      raise :class:`NonFiniteUpdateError` after flushing
                observability — for debugging runs where silent recovery
                would hide the bug.

``max_consecutive`` bounds pathological loops: a run whose every update is
non-finite (true divergence, not a poison batch) escalates to ``halt``
instead of spinning to ``total_steps`` without learning anything.

Metric accounting: ``resilience/skipped_updates``, ``resilience/rollbacks``,
``resilience/nonfinite_updates``, and the ``resilience/goodput_frac`` gauge
(committed updates ÷ attempted updates) all flow through the tracker stream.
"""

from typing import Any, Dict, Optional

from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

POLICIES = ("off", "skip", "rollback", "halt")

# the stats key the device-side check publishes (1.0 = update committed)
UPDATE_OK_KEY = "resilience/update_ok"


class NonFiniteUpdateError(RuntimeError):
    """A non-finite update under the ``halt`` policy (or escalation)."""


class UpdateGuard:
    """Host-side policy half of the update guard (see module docstring)."""

    def __init__(
        self,
        policy: str = "off",
        max_consecutive: int = 25,
        metrics: Any = None,
    ):
        if policy not in POLICIES:
            raise ValueError(
                f"unknown update_guard policy {policy!r} (use one of {POLICIES})"
            )
        self.policy = policy
        self.max_consecutive = int(max_consecutive)
        self.metrics = metrics
        self.consecutive = 0
        self.attempted = 0
        self.committed = 0

    @property
    def enabled(self) -> bool:
        return self.policy != "off"

    def _inc(self, key: str) -> None:
        if self.metrics is not None:
            self.metrics.inc(key)

    def after_step(self, stats: Dict[str, float]) -> Optional[str]:
        """Inspect one step's landed host stats; return the action the learn
        loop must take: ``None`` (continue), ``"rollback"``, or raise
        :class:`NonFiniteUpdateError` for ``halt``/escalation."""
        if not self.enabled:
            return None
        ok = stats.get(UPDATE_OK_KEY, 1.0) >= 0.5
        self.attempted += 1
        if ok:
            self.committed += 1
            self.consecutive = 0
        else:
            self.consecutive += 1
            self._inc("resilience/nonfinite_updates")
        if self.metrics is not None:
            goodput = self.committed / max(self.attempted, 1)
            self.metrics.set_gauge("resilience/goodput_frac", goodput)
        if ok:
            return None
        if self.policy == "halt":
            raise NonFiniteUpdateError(
                "non-finite loss/gradients and update_guard='halt'"
            )
        if self.consecutive >= self.max_consecutive:
            raise NonFiniteUpdateError(
                f"{self.consecutive} consecutive non-finite updates "
                f"(update_guard='{self.policy}', max_consecutive="
                f"{self.max_consecutive}): the run has diverged — halting "
                "instead of spinning"
            )
        if self.policy == "rollback":
            self._inc("resilience/rollbacks")
            logger.warning(
                "non-finite update: rolling back to the newest committed "
                "checkpoint and skipping the poison batch"
            )
            return "rollback"
        self._inc("resilience/skipped_updates")
        logger.warning("non-finite update: skipped (old state kept on device)")
        return None
