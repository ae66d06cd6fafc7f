"""Deterministic fault injection: a parsed :class:`FaultPlan` that trainer,
checkpoint, and host-call sites consult at well-defined points.

Production RL runs die in ways unit tests never exercise: a reward endpoint
times out on call 3, the scheduler SIGTERMs the pod at step 5, one batch
produces a NaN loss at step 7, the process is OOM-killed halfway through a
checkpoint write. The fault plan makes each of those a *reproducible* event:
the same plan string always fires the same faults at the same points, so the
recovery machinery (``trlx_tpu/resilience/``) is testable end-to-end on CPU.

Plan syntax (``;``-separated entries, whitespace ignored)::

    kind@trigger:N[*count]

    kind     one of: reward_raise | publish_raise | sigterm | sigint |
             sigterm_one_proc | nan_loss | crash_save | topology_shrink |
             sleep_one_proc | flightrec_dump | actor_crash |
             weight_sync_drop | health_trip | slow_client | request_flood
    trigger  call  — the Nth invocation of the consulting site (1-based;
                     for reward_raise/publish_raise every *attempt* counts,
                     so retries advance the counter)
             step  — fires when the trainer's completed-update count == N
             save  — the Nth ``save_state`` call (1-based)
             resume — the Nth checkpoint restore (1-based)
             collection — fires when the async actor's collection index
                     == N (1-based; docs/ASYNC_RL.md)
             version — fires when the weight channel publishes params
                     version N
             request — fires when the serve frontend's request id == N
                     (1-based; docs/SERVING.md)
    count    consecutive firings (default 1)

Examples::

    reward_raise@call:3*2        # reward_fn attempts 3 and 4 raise
    sigterm@step:5               # SIGTERM delivered before update 6 starts
    sigterm_one_proc@step:5      # same, but ONLY process 0 is signaled —
                                 # the coordinated-preemption allgather must
                                 # propagate it to the peers
    nan_loss@step:7              # the loss of update 8 is poisoned to NaN
    crash_save@save:2            # the 2nd save_state dies before committing
    topology_shrink@resume:1     # the 1st restore takes the elastic reshard
                                 # path even on a matching mesh
    sleep_one_proc@step:2*3      # the LAST process (highest rank) sleeps
                                 # inside updates 3-5 — a deterministic
                                 # straggler for the cluster-telemetry
                                 # watchdog (cluster/straggler_rank)
    flightrec_dump@step:4        # dump the crash flight recorder at the
                                 # boundary before update 5 (deterministic
                                 # flightrec.json exercise, no crash needed)
    actor_crash@collection:2     # an async generation actor dies at the
                                 # start of its collection-2 chunk — the
                                 # supervisor must requeue the chunk and
                                 # respawn the actor (docs/ASYNC_RL.md)
    weight_sync_drop@version:3   # the weight channel drops the payload of
                                 # params-version-3's publish; actors keep
                                 # the previous params until the next
                                 # publish (deterministic staleness/IW
                                 # exercise)
    health_trip@step:1           # force the RL health monitor to trip at
                                 # the boundary before update 2 — exercises
                                 # the detector → flightrec-dump → bad-batch
                                 # triage path (observability/health.py)
                                 # without needing an organically sick run
    slow_client@request:2        # serve request 2's streaming consumer
                                 # stalls forever — the engine-side producer
                                 # must keep harvesting (bounded stream
                                 # buffer, connection dropped), never wedge
                                 # the slot (docs/RESILIENCE.md, SERVING.md)
    request_flood@step:3         # admission-control drill at the boundary
                                 # before update 4: a synthetic burst is
                                 # pushed through the serve admission path,
                                 # which must shed it with 429s instead of
                                 # letting the queue-wait SLO blow

Plans come from ``config.resilience.fault_plan`` or the
``TRLX_TPU_FAULT_PLAN`` env var (env wins — a relaunched run can drop the
fault by clearing the variable without editing configs). Sites reach the
plan through the module-level *active plan* (:func:`set_active_plan` /
:func:`poll_fault`) so low-level code (``utils/checkpoint.py``) needs no
trainer handle.
"""

import os
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional

_KINDS = frozenset({
    "reward_raise", "publish_raise", "sigterm", "sigint", "sigterm_one_proc",
    "nan_loss", "crash_save", "topology_shrink", "sleep_one_proc",
    "flightrec_dump", "actor_crash", "weight_sync_drop", "health_trip",
    "slow_client", "request_flood",
})

# how long a ``sleep_one_proc`` fault stalls the afflicted rank's train step
# (env-overridable so tests can size the stall above the real step time)
SLEEP_FAULT_S = float(os.environ.get("TRLX_TPU_FAULT_SLEEP_S", "0.5"))
_TRIGGERS = frozenset(
    {"call", "step", "save", "resume", "collection", "version", "request"}
)


class InjectedFault(RuntimeError):
    """Raised by a fault-plan site standing in for a real failure."""


@dataclass(frozen=True)
class FaultSpec:
    """One parsed plan entry: fire ``kind`` for ``count`` consecutive
    trigger values starting at ``n``."""

    kind: str
    trigger: str  # "call" | "step" | "save"
    n: int
    count: int = 1

    def matches(self, value: int) -> bool:
        return self.n <= value < self.n + self.count


@dataclass
class FaultPlan:
    """A set of :class:`FaultSpec` plus per-site call counters.

    ``poll(kind)`` advances the counter for call/save-triggered entries and
    reports whether this invocation should fault; ``poll(kind, step=s)``
    checks step-triggered entries against the caller's step counter without
    advancing anything. Thread-safe: host-call sites poll from pipeline
    worker threads.
    """

    specs: List[FaultSpec] = field(default_factory=list)
    _counters: Dict[str, int] = field(default_factory=dict)
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)
    fired: Dict[str, int] = field(default_factory=dict)

    @classmethod
    def parse(cls, plan: Optional[str]) -> "FaultPlan":
        specs: List[FaultSpec] = []
        for raw in (plan or "").split(";"):
            entry = raw.strip()
            if not entry:
                continue
            try:
                kind, rest = entry.split("@", 1)
                count = 1
                if "*" in rest:
                    rest, count_s = rest.rsplit("*", 1)
                    count = int(count_s)
                trigger, n_s = rest.split(":", 1)
                spec = FaultSpec(kind.strip(), trigger.strip(), int(n_s), count)
            except (ValueError, TypeError) as e:
                raise ValueError(
                    f"unparseable fault-plan entry {entry!r} (syntax: "
                    f"kind@trigger:N[*count], docs/RESILIENCE.md): {e}"
                ) from e
            if spec.kind not in _KINDS:
                raise ValueError(
                    f"unknown fault kind {spec.kind!r} (known: {sorted(_KINDS)})"
                )
            if spec.trigger not in _TRIGGERS:
                raise ValueError(
                    f"unknown fault trigger {spec.trigger!r} "
                    f"(known: {sorted(_TRIGGERS)})"
                )
            if spec.count < 1 or spec.n < 0:
                raise ValueError(f"fault-plan entry {entry!r}: n/count out of range")
            specs.append(spec)
        return cls(specs=specs)

    @classmethod
    def from_config(cls, plan: Optional[str]) -> "FaultPlan":
        """Parse ``plan``, letting ``TRLX_TPU_FAULT_PLAN`` override it."""
        return cls.parse(os.environ.get("TRLX_TPU_FAULT_PLAN") or plan)

    def __bool__(self) -> bool:
        return bool(self.specs)

    def due(self, step: int) -> bool:
        """Whether any entry triggers on update ``step``, of whatever kind
        (nothing advances, nothing fires): the learn loop launches such a
        step with nothing else in flight."""
        return any(s.trigger == "step" and s.matches(step) for s in self.specs)

    def poll(
        self,
        kind: str,
        step: Optional[int] = None,
        collection: Optional[int] = None,
        version: Optional[int] = None,
        request: Optional[int] = None,
    ) -> bool:
        """Should the consulting site fault now?

        With no caller counter this is an *invocation* poll: the per-kind
        call counter advances by one and call/save/resume-triggered entries
        match against it. With ``step=s`` / ``collection=c`` / ``version=v``
        / ``request=r`` only the matching trigger's entries are checked
        against the caller's own counter (idempotent — the caller polls
        once per update / collection / publish / serve request)."""
        if not self.specs:
            return False
        with self._lock:
            if step is not None:
                value, triggers = step, ("step",)
            elif collection is not None:
                value, triggers = collection, ("collection",)
            elif version is not None:
                value, triggers = version, ("version",)
            elif request is not None:
                value, triggers = request, ("request",)
            else:
                value = self._counters.get(kind, 0) + 1
                self._counters[kind] = value
                triggers = ("call", "save", "resume")
            hit = any(
                s.kind == kind and s.trigger in triggers and s.matches(value)
                for s in self.specs
            )
            if hit:
                self.fired[kind] = self.fired.get(kind, 0) + 1
            return hit


# ---------------------------------------------------------------------------
# process-wide active plan: low-level sites (checkpoint commit) consult this
# without a trainer handle. One training run per process is the norm; the
# last-constructed Resilience bundle owns the slot.
# ---------------------------------------------------------------------------

_ACTIVE_PLAN: Optional[FaultPlan] = None


def set_active_plan(plan: Optional[FaultPlan]) -> None:
    global _ACTIVE_PLAN
    _ACTIVE_PLAN = plan if plan else None


def get_active_plan() -> Optional[FaultPlan]:
    return _ACTIVE_PLAN


def poll_fault(
    kind: str, step: Optional[int] = None, request: Optional[int] = None
) -> bool:
    """Convenience for sites without a plan handle; False when no plan."""
    plan = _ACTIVE_PLAN
    return bool(plan) and plan.poll(kind, step=step, request=request)
