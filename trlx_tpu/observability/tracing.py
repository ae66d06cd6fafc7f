"""Lightweight span tracer for the runtime (host wall-time, device-fenced).

The trainer's timers historically clocked JAX's *async dispatch* — the host
returns from a jitted call long before the device finishes. A :class:`Span`
therefore carries an optional **fence**: a pytree of device arrays that is
``jax.block_until_ready``-ed at span exit, so the recorded duration is
device-true execution time, not dispatch latency.

Spans nest (a thread-local stack), are rank-aware (every event records
``jax.process_index()`` as its Chrome-trace ``pid``), and land in two places:

- the tracer's own bounded buffer, exported by ``export_chrome_trace(path)``
  as a Chrome/Perfetto ``trace.json`` (complete ``"ph": "X"`` events;
  containment on one ``tid`` renders as nesting);
- the profiler's clock: every span also opens a
  ``jax.profiler.TraceAnnotation("trlx/<name>")`` for its whole life, fence
  included, so that while a ``jax.profiler`` session is open (the
  ``TRLX_TPU_PROFILE`` window, a benchmark's traced run) the span sits on the
  trace's ``/host:CPU`` plane beside the device's ``XLA Ops``, from worker
  threads too. With no session open the annotation is inert.

Usage::

    from trlx_tpu.observability import span

    with span("rollout"):
        with span("generate") as sp:
            out = generate(...)
            sp.fence(out.sequences)   # block on device work at exit
"""

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, Iterator, List, Optional, Union

FenceLike = Union[None, Any, Callable[[], Any]]

# prefix of every program span on the profiler's host plane
PROFILER_PREFIX = "trlx/"


def _process_index() -> int:
    # lazy: importing/initializing jax at module import would race the
    # platform-selection env vars set by conftest/initialize_runtime
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return 0


def _block(tree: Any) -> None:
    import jax

    jax.block_until_ready(tree)


def _profiler_annotation(name: str, args: Dict[str, Any]):
    """The span's twin on the profiler's clock; the span's args ride along
    as the event's stats. Costs a no-op C++ call while no session is open."""
    import jax

    return jax.profiler.TraceAnnotation(PROFILER_PREFIX + name, **args)


class Span:
    """One timed region. ``duration`` is valid after the span closes."""

    __slots__ = ("name", "depth", "args", "t0", "t1", "_fence")

    def __init__(self, name: str, depth: int, args: Optional[Dict[str, Any]] = None):
        self.name = name
        self.depth = depth
        self.args = args or {}
        self.t0 = time.perf_counter()
        self.t1: Optional[float] = None
        self._fence: FenceLike = None

    def fence(self, tree: FenceLike) -> "Span":
        """Set the device pytree to ``block_until_ready`` at span exit."""
        self._fence = tree
        return self

    @property
    def duration(self) -> float:
        """Seconds, device-fenced if a fence was set. 0.0 while open."""
        return (self.t1 - self.t0) if self.t1 is not None else 0.0

    def close(self) -> float:
        if self._fence is not None:
            _block(self._fence() if callable(self._fence) else self._fence)
        self.t1 = time.perf_counter()
        return self.duration


class Tracer:
    """Collects closed spans as Chrome-trace-shaped events.

    Thread-safe for recording; the span *stack* is thread-local so spans
    opened on different threads nest independently. The event buffer is
    bounded (``max_events``): past the cap, events are dropped and counted
    rather than growing without limit over a long run.
    """

    def __init__(self, enabled: bool = True, max_events: int = 200_000):
        self.enabled = enabled
        self.max_events = max_events
        self._lock = threading.Lock()
        # spans close on pipeline worker threads too: every mutation of the
        # shared buffers below takes the lock (enforced statically by
        # graftlint's lock-discipline pass, docs/STATIC_ANALYSIS.md)
        self.dropped = 0  # guarded-by: _lock
        self._events: List[Dict[str, Any]] = []  # guarded-by: _lock
        self._local = threading.local()
        # the collect-plus-learn cycle the trainer is in (set by the main
        # thread at the start of each collection): stamped into the args of
        # every span opened meanwhile, on any thread
        self.cycle: Optional[int] = None
        self._epoch = time.perf_counter()
        self._last_duration: Dict[str, float] = {}  # guarded-by: _lock
        # event listeners (the crash flight recorder): called for EVERY
        # event, including ones the bounded buffer drops — the recorder's
        # own ring keeps rotating after the tracer cap is hit, which is
        # exactly when a long run crashes
        self._listeners: List[Callable[[Dict[str, Any]], None]] = []  # guarded-by: _lock

    # -- recording ------------------------------------------------------

    def _stack(self) -> List[Span]:
        if not hasattr(self._local, "stack"):
            self._local.stack = []
        return self._local.stack

    def _tid(self) -> int:
        return getattr(
            self._local, "tid", None
        ) or threading.get_ident() % 2**31

    def next_cycle(self) -> None:
        """Begin the next collect-plus-learn cycle (the trainer calls this
        at the start of every collection)."""
        self.cycle = (self.cycle or 0) + 1

    def alias_current_thread(self, alias: str) -> None:
        """Record this thread's events under a stable pseudo-tid derived
        from ``alias`` instead of the OS thread id. Short-lived workers that
        recur under one role — e.g. the rollout pipeline spawns one worker
        per ``make_experience`` call — then share a single named track in
        the Chrome/Perfetto export instead of scattering one near-empty row
        per incarnation. Emits the ``thread_name`` metadata event once per
        alias so the track is labeled in the viewer."""
        self._local.tid = self._track_tid(alias)

    @contextmanager
    def span(  # acquires: span
        self, name: str, fence: FenceLike = None, **args: Any
    ) -> Iterator[Span]:
        """Open a nested span; closes (and fences) on exit even on error.

        Declared to graftlint's ownership pass (GL80x): the idiomatic
        ``with tracer.span(...):`` is release-covered by ``__exit__``; a
        bare call that stashes (or discards) the context manager without
        entering it leaks the open span and is a finding."""
        stack = self._stack()
        if self.cycle is not None:
            args.setdefault("cycle", self.cycle)
        with _profiler_annotation(name, args):  # closes after the fence
            sp = Span(name, depth=len(stack), args=args)
            if fence is not None:
                sp.fence(fence)
            stack.append(sp)
            try:
                yield sp
            finally:
                # remove *this* span (not blindly the top): an exception that
                # unwinds past a manually-entered inner span must not corrupt
                # the depth bookkeeping of outer spans
                if sp in stack:
                    stack.remove(sp)
                dur = sp.close()
                with self._lock:  # worker + main thread both close spans
                    self._last_duration[name] = dur
                if self.enabled:
                    self._record(sp)

    def instant(self, name: str, **args: Any) -> None:
        """A zero-duration marker event (Chrome-trace ``"ph": "i"``)."""
        if not self.enabled:
            return
        event = {
            "name": name,
            "ph": "i",
            "ts": (time.perf_counter() - self._epoch) * 1e6,
            "pid": _process_index(),
            "tid": self._tid(),
            "s": "t",
        }
        if args:
            event["args"] = args
        self._append(event)

    def _record(self, sp: Span) -> None:
        event = {
            "name": sp.name,
            "ph": "X",
            "ts": (sp.t0 - self._epoch) * 1e6,
            "dur": (sp.t1 - sp.t0) * 1e6,
            "pid": _process_index(),
            "tid": self._tid(),
        }
        if sp.args:
            event["args"] = dict(sp.args)
        self._append(event)

    def _append(self, event: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
            else:
                self._events.append(event)
            listeners = list(self._listeners)
        # listeners run OUTSIDE the lock (a listener touching the tracer
        # must not deadlock) and are never allowed to break recording
        for fn in listeners:
            try:
                fn(event)
            except Exception:  # pragma: no cover - defensive
                pass

    def add_listener(self, fn: Callable[[Dict[str, Any]], None]) -> None:
        """Subscribe to every recorded (or cap-dropped) event — the crash
        flight recorder's tap (``observability/flightrec.py``)."""
        with self._lock:
            self._listeners.append(fn)

    def _track_tid(self, alias: str) -> int:
        """Stable pseudo-tid for a named track, emitting the labeling
        ``thread_name`` metadata event once per alias (shared by
        :meth:`alias_current_thread` and :meth:`add_complete_event`)."""
        import zlib

        tid = zlib.crc32(alias.encode()) % 2**31 or 1
        if not self.enabled:
            return tid
        with self._lock:
            seen = getattr(self, "_aliased", None)
            if seen is None:
                seen = self._aliased = set()
            if alias in seen:
                return tid
            seen.add(alias)
        self._append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": _process_index(),
                "tid": tid,
                "args": {"name": alias},
            }
        )
        return tid

    def add_complete_event(
        self, name: str, t0: float, t1: float, track: Optional[str] = None,
        **args: Any,
    ) -> None:
        """Record a complete (``"ph": "X"``) event with *explicit*
        ``time.perf_counter`` endpoints — for retrospective spans whose
        boundaries were only known after the fact (the Engine's per-request
        lifecycle: queue wait → prefill → decode, emitted at harvest).
        ``track`` names a stable pseudo-thread row in the viewer."""
        if not self.enabled:
            return
        event: Dict[str, Any] = {
            "name": name,
            "ph": "X",
            "ts": (t0 - self._epoch) * 1e6,
            "dur": max(t1 - t0, 0.0) * 1e6,
            "pid": _process_index(),
            "tid": self._track_tid(track) if track else self._tid(),
        }
        if args:
            event["args"] = dict(args)
        self._append(event)

    # -- reading / export ----------------------------------------------

    def last_duration(self, name: str, default: float = 0.0) -> float:
        """Duration of the most recently closed span named ``name``."""
        return self._last_duration.get(name, default)

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events = []
            self.dropped = 0

    def to_chrome_trace(self) -> Dict[str, Any]:
        meta = {"dropped_events": self.dropped} if self.dropped else {}
        return {"traceEvents": self.events(), "displayTimeUnit": "ms", **meta}

    def export_chrome_trace(self, path: str) -> str:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


# ---------------------------------------------------------------------------
# module-level default tracer (library users without a trainer)
# ---------------------------------------------------------------------------

_DEFAULT_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _DEFAULT_TRACER


@contextmanager
def span(name: str, fence: FenceLike = None, **args: Any) -> Iterator[Span]:
    """``with span("rollout"): ...`` on the module-level default tracer."""
    with _DEFAULT_TRACER.span(name, fence=fence, **args) as sp:
        yield sp
