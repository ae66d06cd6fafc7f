"""Programmatic ``jax.profiler`` trace windows + step annotations.

A pod run can't afford an always-on profiler, but "attach a profiler for
steps 3-5" must not require a code change. The window comes from either:

- ``TRLX_TPU_PROFILE=steps:3-5,dir:/tmp/trace`` — an env var, so any
  launcher can arm a window without touching configs; or
- ``config.train.profile_dir`` — the pre-existing config knob, which keeps
  its historical window (steps 1-4).

While a window is open, the learn loop also wraps each unit of device work
in ``jax.profiler.StepTraceAnnotation`` so the trace viewer groups ops by
train/generate step.
"""

import os
from contextlib import nullcontext
from typing import Any, Optional, Tuple

from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

PROFILE_ENV = "TRLX_TPU_PROFILE"


def parse_profile_spec(spec: str) -> Tuple[int, int, str]:
    """``"steps:3-5,dir:/tmp/x"`` → ``(3, 5, "/tmp/x")``.

    ``steps:N`` (single step) means ``N-N``; ``dir`` defaults to
    ``/tmp/trlx_tpu_profile``. Raises ``ValueError`` on a malformed spec.
    """
    start, stop, directory = None, None, "/tmp/trlx_tpu_profile"
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        key, _, value = part.partition(":")
        if key == "steps":
            lo, _, hi = value.partition("-")
            start = int(lo)
            stop = int(hi) if hi else start
        elif key == "dir":
            directory = value
        else:
            raise ValueError(f"unknown {PROFILE_ENV} field '{key}' in '{spec}'")
    if start is None:
        raise ValueError(f"{PROFILE_ENV} needs a steps:<a>-<b> field, got '{spec}'")
    if stop < start:
        raise ValueError(f"{PROFILE_ENV} steps window is inverted: '{spec}'")
    return start, stop, directory


class ProfileWindow:
    """Starts/stops one ``jax.profiler`` trace around a step interval.

    ``on_step_start(step)`` / ``on_step_end(step)`` bracket each unit of
    work with the trainer's *pre-increment* step index; the window traces
    steps ``start..stop`` inclusive. ``stop()`` is an idempotent final
    close for early-exit paths. A disabled window (no spec) is all no-ops.
    """

    def __init__(self, start: Optional[int], stop: Optional[int], directory: Optional[str]):
        self.start = start
        self.stop_step = stop
        self.directory = directory
        self.active = False
        self._done = False

    @classmethod
    def disabled(cls) -> "ProfileWindow":
        return cls(None, None, None)

    @classmethod
    def from_env(cls, config: Any = None) -> "ProfileWindow":
        spec = os.environ.get(PROFILE_ENV)
        if spec:
            try:
                start, stop, directory = parse_profile_spec(spec)
                return cls(start, stop, directory)
            except ValueError as e:
                logger.warning("ignoring malformed %s: %s", PROFILE_ENV, e)
        profile_dir = getattr(getattr(config, "train", None), "profile_dir", None)
        if profile_dir:
            # historical config behavior: trace the window after the first
            # warmup step (pre-increment steps 1..4)
            return cls(1, 4, profile_dir)
        return cls.disabled()

    @property
    def enabled(self) -> bool:
        return self.start is not None

    def starts_at(self, step: int) -> bool:
        """Whether ``on_step_start(step)`` would open the trace."""
        return (self.enabled and not self.active and not self._done
                and self.start <= step <= self.stop_step)

    def stops_at(self, step: int) -> bool:
        """Whether ``on_step_end(step)`` would close it. The learn loop has
        no other step in flight at either edge: the trace holds whole steps."""
        return self.active and step >= self.stop_step

    def on_step_start(self, step: int) -> None:
        if self.starts_at(step):
            import jax

            logger.info(
                "profiler: starting trace at step %d (window %d-%d) -> %s",
                step, self.start, self.stop_step, self.directory,
            )
            jax.profiler.start_trace(self.directory)
            self.active = True

    def on_step_end(self, step: int) -> None:
        if self.stops_at(step):
            self.stop()
            self._done = True

    def stop(self) -> None:
        if not self.active:
            return
        import jax

        jax.profiler.stop_trace()
        self.active = False
        logger.info("profiler: trace written to %s", self.directory)

    def step_annotation(self, name: str, step: int):
        """``StepTraceAnnotation`` context while the window is open (a
        no-op context otherwise, so the hot loop never pays for it)."""
        if not self.active:
            return nullcontext()
        import jax

        return jax.profiler.StepTraceAnnotation(name, step_num=step)
