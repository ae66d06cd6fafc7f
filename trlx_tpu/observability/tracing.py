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

    tracer = Tracer()                 # a trainer's is ``trainer.obs.tracer``
    with tracer.span("rollout"):
        with tracer.span("generate") as sp:
            out = generate(...)
            sp.fence(out.sequences)   # block on device work at exit

**What happened beneath a span.** The span tree stops at the program's own
Python. What the JAX runtime, the interpreter and the operating system did
meanwhile reaches it through one sink, :func:`attribute`: seconds of a
``kind`` over an interval, added to the process's cumulative totals
(:func:`mark` reads them; the difference of two marks describes the interval
between them) and handed to every live :meth:`Tracer.attribute`, which adds
them to the innermost span open on the thread they happened on and records
them as a retrospective child event. The sources are registered once a
process (:func:`install_sources`):

- the runtime, through ``jax.monitoring``: ``runtime/trace``,
  ``runtime/lower``, ``runtime/compile`` (the true compile: less the load)
  and ``runtime/cache_load``, outermost events only, each with its
  program's ``fun_name``; the counts ``runtime/programs`` (backend compile
  events), ``runtime/cache_hits`` (executables loaded from the persistent
  cache) and ``runtime/cache_misses`` (compiled and written to it): set-up's
  account reads all three (``Observability.freeze_setup``);
- the program store (``utils/programs.py``), which calls the sink itself:
  ``runtime/store_load`` (seconds reading and loading a kept executable, with
  its program's name) and ``runtime/store_write`` (digesting, serializing and
  writing one after a miss), and the counts ``runtime/store_hits`` and
  ``runtime/store_misses``. A hit raises none of the runtime's own events;
- the interpreter, through ``gc.callbacks``: ``host/gc`` with the generation;
- the fence: a fenced span knows ``dispatch`` (open to fence) from ``wait``;
- the operating system, in :func:`mark` (the trainer takes one where a
  collection and a train step end): the calling thread's CPU seconds,
  involuntary context switches and major faults (every record carries the
  three, and the slow-interval line prints them), and the whole process's
  CPU seconds and switches.
  Not at every cycle-level span's two ends: a system call costs 10 us on the
  chip's host, and the records' intervals end where those spans do.
"""

import gc
import json
import os
import threading
import time
import weakref
from collections import deque
from contextlib import contextmanager
from typing import Any, Callable, Deque, Dict, Iterator, List, Optional, Sequence, Tuple, Union

try:  # POSIX only; the thread's counters need Linux's RUSAGE_THREAD
    import resource
except ImportError:  # pragma: no cover - not a platform this runs on
    resource = None

FenceLike = Union[None, Any, Callable[[], Any]]

# prefix of every program span on the profiler's host plane
PROFILER_PREFIX = "trlx/"

# a garbage collection becomes an event of its own from here up (and every
# generation-2 one does): the young generations run hundreds of times a
# second for tens of microseconds and would fill the buffers; their seconds
# still count in the totals and on the span
GC_EVENT_MIN_S = 1e-3

# jax.monitoring stamps time.time(); spans live on perf_counter
_WALL_TO_PERF = time.perf_counter() - time.time()

_RUNTIME_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "runtime/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "runtime/lower",
    "/jax/core/compile/backend_compile_duration": "runtime/compile",
}
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_COUNTS = {
    "/jax/compilation_cache/cache_hits": "runtime/cache_hits",
    "/jax/compilation_cache/cache_misses": "runtime/cache_misses",
}


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


def thread_usage() -> Tuple[float, int, int]:
    """CPU seconds, involuntary context switches and major faults of the
    calling thread so far. ONE system call: on the chip's host one costs 7
    to 10 us (PERF.md section 6, PR 35), so the CPU seconds are the same
    call's user plus system time and not ``time.thread_time()`` beside it."""
    if resource is None or not hasattr(resource, "RUSAGE_THREAD"):
        return time.thread_time(), 0, 0
    ru = resource.getrusage(resource.RUSAGE_THREAD)
    return ru.ru_utime + ru.ru_stime, ru.ru_nivcsw, ru.ru_majflt


def process_usage() -> Tuple[float, int]:
    """CPU seconds and involuntary context switches of every thread of the
    process so far: the runtime's own threads launch the programs, and one
    of them descheduled looks, from the thread at the fence, like a wait."""
    if resource is None:
        return time.process_time(), 0
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime + ru.ru_stime, ru.ru_nivcsw


# ---------------------------------------------------------------------------
# the sink and its sources (process-wide: the runtime, the collector and the
# scheduler act on the process, whichever trainer's span is open)
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_TOTALS: Dict[str, float] = {}  # kind -> seconds or count; guarded by _LOCK
# fun_name -> kind -> seconds (the set-up table); guarded by _LOCK
_PROGRAMS: Dict[str, Dict[str, float]] = {}
# (end, kind, fun_name, seconds) of the newest runtime events: which program
# a slow interval retraced
_RECENT: Deque[Tuple[float, str, str, float]] = deque(maxlen=64)
# seconds, generation-2 collections. Written by the collector's callback
# alone, which the interpreter never runs twice at once, and WITHOUT a lock:
# a collection can begin at any bytecode of this very thread, one that holds
# _LOCK included
_GC = [0.0, 0]
_gc_open: Optional[Tuple[float, Any]] = None  # (start, profiler annotation)
_TRACERS: List["weakref.ref[Tracer]"] = []
_TraceAnnotation: Any = None  # jax.profiler's, looked up once (install_sources)
_RUNTIME = threading.local()  # .depth of open runtime events, .cache_load seconds pending
_sources_installed = False


def _live_tracers() -> List["Tracer"]:
    return [t for t in (ref() for ref in _TRACERS) if t is not None]


def attribute(kind: str, t0: float, t1: float, seconds: Optional[float] = None,
              **args: Any) -> None:
    """THE sink: ``seconds`` of ``kind`` (``t1 - t0`` unless the interval
    holds other kinds' seconds too) over ``[t0, t1]`` on ``perf_counter``,
    on the calling thread. Not for the collector's callback
    (:func:`_on_gc`), which may take no lock."""
    dt = max(t1 - t0, 0.0) if seconds is None else seconds
    fun = args.get("fun_name")
    with _LOCK:
        _TOTALS[kind] = _TOTALS.get(kind, 0.0) + dt
        if fun is not None:
            row = _PROGRAMS.setdefault(fun, {})
            row[kind] = row.get(kind, 0.0) + dt
            _RECENT.append((t1, kind, fun, dt))
    for tracer in _live_tracers():
        tracer.attribute(kind, t0, t1, seconds=dt, **args)


def count(kind: str, n: int = 1) -> None:
    """One more of a counted kind (``runtime/cache_hits``)."""
    with _LOCK:
        _TOTALS[kind] = _TOTALS.get(kind, 0.0) + n


def mark() -> Dict[str, float]:
    """The cumulative totals now, with the calling thread's counters
    (``host/cpu_s``, ``host/invol_switches``, ``host/major_faults``), the
    whole process's (``host/proc_cpu_s``, ``host/proc_invol_switches``) and
    the clock (``t``). ``since(m0, m1)`` of two marks taken on one thread
    describes the interval between them, on whichever thread the seconds
    fell."""
    with _LOCK:
        m = dict(_TOTALS)
    m["host/gc"], m["host/gc_gen2"] = _GC
    m["host/cpu_s"], m["host/invol_switches"], m["host/major_faults"] = thread_usage()
    m["host/proc_cpu_s"], m["host/proc_invol_switches"] = process_usage()
    m["t"] = time.perf_counter()
    return m


def since(m0: Dict[str, float], m1: Dict[str, float]) -> Dict[str, float]:
    """What was added between two marks, key by key."""
    return {k: v - m0.get(k, 0.0) for k, v in m1.items()}


def programs() -> Dict[str, Dict[str, float]]:
    """``fun_name -> kind -> seconds`` of every program the runtime traced,
    lowered, compiled or loaded so far."""
    with _LOCK:
        return {fun: dict(row) for fun, row in _PROGRAMS.items()}


def programs_table(before: Dict[str, Dict[str, float]], rows: int = 12,
                   held: Optional[Dict[str, Sequence[float]]] = None) -> str:
    """The runtime's seconds by program since ``before`` (an earlier
    :func:`programs`): the ``rows`` costliest by name, the rest (the eager
    operations of ``init``, mostly) in one row. Behind the seconds, for the
    programs ``held`` names (``utils/programs.py::ProgramStore.account``'s
    ``by_program``: the job's own store, walked now; blank for any other), what
    their executables hold on the device: how many are resident, their code in
    MiB, the largest temporaries and the largest arguments + outputs - aliased
    in GiB. The row with the large temporaries sizes what the runtime reserves
    on top of the allocator's peak."""
    kinds = ("runtime/trace", "runtime/lower", "runtime/compile", "runtime/cache_load",
             "runtime/store_load")
    held = held or {}
    table = []
    # (a held program is in the table even where the sink has no second of it)
    for fun, row in {**dict.fromkeys(held, {}), **programs()}.items():
        old = before.get(fun, {})
        new = [row.get(k, 0.0) - old.get(k, 0.0) for k in ("programs",) + kinds]
        if any(new) or fun in held:
            table.append((fun, new, held.get(fun)))
    table.sort(key=lambda r: -sum(r[1][1:]))
    rest = table[rows:]
    if rest:
        kept = [r[2] for r in rest if r[2]]
        table = table[:rows] + [(
            f"{len(rest)} others", [sum(r[1][i] for r in rest) for i in range(6)],
            [sum(h[0] for h in kept), sum(h[1] for h in kept), max(h[2] for h in kept),
             max(h[3] for h in kept)] if kept else None)]
    lines = [f"{'program':<32}{'compiled':>9}{'trace':>9}{'lower':>9}{'compile':>9}{'load':>9}"
             f"{'store':>9}{'resident':>9}{'code MiB':>9}{'temp GiB':>9}{'live GiB':>9}"]
    for fun, (n, *seconds), row in table:
        line = f"{fun[:31]:<32}{int(n):>9}" + "".join(f"{x:>9.3f}" for x in seconds)
        if row:
            line += f"{int(row[0]):>9}{row[1] / 2**20:>9.1f}{row[2] / 2**30:>9.3f}{row[3] / 2**30:>9.3f}"
        lines.append(line)
    return "\n".join(lines)


def recent_programs(t0: float, t1: float) -> List[str]:
    """Names of the programs with a runtime event that ended in ``[t0, t1]``
    (of the newest 64 events), in order, each once."""
    with _LOCK:
        hits = [fun for end, _, fun, _ in _RECENT if t0 <= end <= t1]
    return list(dict.fromkeys(hits))


def _on_runtime_begin(event: str, value: float, **kw: Any) -> None:
    # jax records an event's start as a scalar under the event's name
    if event in _RUNTIME_KINDS:
        _RUNTIME.depth = getattr(_RUNTIME, "depth", 0) + 1


def _on_runtime_span(event: str, start: float, end: float, **kw: Any) -> None:
    kind = _RUNTIME_KINDS.get(event)
    if kind is None:
        return
    _RUNTIME.depth = depth = max(getattr(_RUNTIME, "depth", 1) - 1, 0)
    if depth:
        # tracing a program traces the jitted functions it calls (thousands
        # of events a train step) and may compile an eager operation: only
        # the outermost event counts, under its program's name
        _RUNTIME.cache_load = 0.0
        return
    t0, t1 = start + _WALL_TO_PERF, end + _WALL_TO_PERF
    fun = str(kw.get("fun_name", "?"))
    if fun.startswith("jit(") and fun.endswith(")"):
        fun = fun[4:-1]  # lowering and compiling say jit(f) where tracing says f
    load = 0.0
    if kind == "runtime/compile":
        count("runtime/programs")
        with _LOCK:
            row = _PROGRAMS.setdefault(fun, {})
            row["programs"] = row.get("programs", 0) + 1
        load, _RUNTIME.cache_load = getattr(_RUNTIME, "cache_load", 0.0), 0.0
        if load:  # the executable came from the persistent cache
            attribute("runtime/cache_load", t1 - load, t1, fun_name=fun)
    # a compile's own seconds are the true compile: the interval less the load
    attribute(kind, t0, t1, seconds=max(end - start - load, 0.0), fun_name=fun)


def _on_runtime_duration(event: str, duration: float, **kw: Any) -> None:
    # reported inside the compile event it belongs to, before that ends
    if event == _CACHE_LOAD_EVENT:
        _RUNTIME.cache_load = getattr(_RUNTIME, "cache_load", 0.0) + duration


def _on_runtime_event(event: str, **kw: Any) -> None:
    # fired inside the compile event the executable belongs to; like
    # runtime/programs, only an outermost compile's counts
    kind = _CACHE_COUNTS.get(event)
    if kind == "runtime/cache_hits":
        _RUNTIME.cache_hits = getattr(_RUNTIME, "cache_hits", 0) + 1
    if kind is not None and getattr(_RUNTIME, "depth", 0) <= 1:
        count(kind)


def thread_cache_hits() -> int:
    """Executables the persistent compile cache has given THIS thread so far:
    the program store asks around a compile whether its executable was compiled
    here or loaded (``utils/programs.py::_compile_and_write``)."""
    return getattr(_RUNTIME, "cache_hits", 0)


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    """``gc.callbacks`` entry. Takes no lock and calls nothing that does: it
    runs wherever a collection begins, inside the tracer's and the flight
    recorder's locked sections too. The event is queued and recorded by the
    tracer's next span (:meth:`Tracer._flush_gc`)."""
    global _gc_open
    if phase == "start":
        annotation = None
        if info["generation"]:
            # inert unless a profiler session is open; then host_gaps.py puts
            # a device idle gap down to the collection (the youngest
            # generation runs for tens of microseconds: no gap of its own)
            annotation = _TraceAnnotation(PROFILER_PREFIX + "host/gc",
                                          generation=info["generation"])
            annotation.__enter__()
        _gc_open = (time.perf_counter(), annotation)
        return
    if _gc_open is None:  # installed between a collection's start and stop
        return
    t1 = time.perf_counter()
    t0, annotation = _gc_open
    _gc_open = None
    if annotation is not None:
        annotation.__exit__(None, None, None)
    generation = info["generation"]
    _GC[0] += t1 - t0
    _GC[1] += generation == 2
    for tracer in _live_tracers():
        tracer._note_gc(t0, t1, generation)


def install_sources() -> None:
    """Register the runtime's and the collector's listeners, once a process
    (``initialize_runtime`` and every :class:`Tracer` call this)."""
    global _sources_installed
    with _LOCK:
        if _sources_installed:
            return
        _sources_installed = True
    global _TraceAnnotation
    import jax
    import jax.monitoring as monitoring

    _TraceAnnotation = jax.profiler.TraceAnnotation
    monitoring.register_scalar_listener(_on_runtime_begin)
    monitoring.register_event_time_span_listener(_on_runtime_span)
    monitoring.register_event_duration_secs_listener(_on_runtime_duration)
    monitoring.register_event_listener(_on_runtime_event)
    gc.callbacks.append(_on_gc)


def uninstall_sources() -> None:
    """Take the listeners out again (tests)."""
    global _sources_installed
    with _LOCK:
        if not _sources_installed:
            return
        _sources_installed = False
    import jax.monitoring as monitoring

    monitoring.unregister_scalar_listener(_on_runtime_begin)
    monitoring.unregister_event_time_span_listener(_on_runtime_span)
    monitoring.unregister_event_duration_listener(_on_runtime_duration)
    monitoring.unregister_event_listener(_on_runtime_event)
    gc.callbacks.remove(_on_gc)


class Span:
    """One timed region. ``duration`` is valid after the span closes."""

    __slots__ = ("name", "depth", "args", "t0", "t1", "t_fence", "t_ready", "_fence", "attributed")

    def __init__(self, name: str, depth: int, args: Optional[Dict[str, Any]] = None):
        self.name = name
        self.depth = depth
        self.args = args or {}
        self.t1: Optional[float] = None
        self.t_fence: Optional[float] = None  # when the fence began
        self.t_ready: Optional[float] = None  # when it returned
        self._fence: FenceLike = None
        # kind -> seconds attributed to this span while it was the innermost
        # one open on its thread (allocated on first use)
        self.attributed: Optional[Dict[str, float]] = None
        self.t0 = time.perf_counter()

    def fence(self, tree: FenceLike) -> "Span":
        """Set the device pytree to ``block_until_ready`` at span exit."""
        self._fence = tree
        return self

    def block(self, tree: FenceLike) -> float:
        """Fence on ``tree`` now, inside the span, which goes on (the learn
        loop's landing: its record follows its fence); when it returned."""
        self.t_fence = time.perf_counter()
        _block(tree() if callable(tree) else tree)
        self.t_ready = time.perf_counter()
        return self.t_ready

    @property
    def duration(self) -> float:
        """Seconds, device-fenced if a fence was set. 0.0 while open."""
        return (self.t1 - self.t0) if self.t1 is not None else 0.0

    @property
    def wait(self) -> float:
        """Seconds the host spent in the fence with nothing left to do; 0.0
        without a fence."""
        return (self.t_ready - self.t_fence) if self.t_ready is not None else 0.0

    @property
    def dispatch(self) -> float:
        """Seconds of the span outside its fence: the host was still setting
        up, placing arguments, enqueueing, or in anything ``attributed``
        names. ``dispatch + wait == duration``."""
        return self.duration - self.wait

    def add(self, kind: str, seconds: float) -> None:
        if self.attributed is None:
            self.attributed = {}
        self.attributed[kind] = self.attributed.get(kind, 0.0) + seconds

    def close(self) -> float:
        self.t1 = self.block(self._fence) if self._fence is not None else time.perf_counter()
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
        # collections waiting to become events: appended by the collector's
        # callback (list.append, no lock: see _on_gc), drained by _flush_gc
        self._gc_pending: List[Tuple[float, float, int, int]] = []
        self._rank: Optional[int] = None
        # event listeners (the crash flight recorder): called for EVERY
        # event, including ones the bounded buffer drops — the recorder's
        # own ring keeps rotating after the tracer cap is hit, which is
        # exactly when a long run crashes
        self._listeners: List[Callable[[Dict[str, Any]], None]] = []  # guarded-by: _lock
        install_sources()
        with _LOCK:
            _TRACERS[:] = [ref for ref in _TRACERS if ref() is not None]
            _TRACERS.append(weakref.ref(self))

    # -- recording ------------------------------------------------------

    def _stack(self) -> List[Span]:
        if not hasattr(self._local, "stack"):
            self._local.stack = []
        return self._local.stack

    def _tid(self) -> int:
        try:
            return self._local.tid
        except AttributeError:  # once a thread: a missing attribute is the slow path
            tid = self._local.tid = threading.get_ident() % 2**31
            return tid

    def _pid(self) -> int:
        # the rank cannot change once the backend is up, which the first
        # call sees to; looked up once, not at every event
        if self._rank is None:
            self._rank = _process_index()
        return self._rank

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
        # the span's twin on the profiler's clock, its args as the event's
        # stats; a no-op C++ call while no session is open. Closes after the fence
        with _TraceAnnotation(PROFILER_PREFIX + name, **args):
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
                sp.close()
                if self.enabled:
                    self._record(sp)

    def attribute(self, kind: str, t0: float, t1: float, seconds: Optional[float] = None,
                  **args: Any) -> None:
        """This tracer's end of the sink (:func:`attribute`): the seconds go
        to the innermost span open on the calling thread, if there is one,
        and ``[t0, t1]`` becomes a retrospective child event."""
        stack = self._stack()
        if stack:
            stack[-1].add(kind, max(t1 - t0, 0.0) if seconds is None else seconds)
        self.add_complete_event(kind, t0, t1, **args)

    def _note_gc(self, t0: float, t1: float, generation: int) -> None:
        """From the collector's callback: no lock, nothing recorded yet."""
        stack = getattr(self._local, "stack", None)
        if stack:
            stack[-1].add("host/gc", t1 - t0)
        if (self.enabled and (generation == 2 or t1 - t0 >= GC_EVENT_MIN_S)
                and len(self._gc_pending) < 1024):  # a tracer nobody opens spans on
            self._gc_pending.append((t0, t1, generation, self._tid()))

    def _flush_gc(self) -> None:
        while True:
            try:  # spans close on two threads at once: the pop is atomic, a test before it is not
                t0, t1, generation, tid = self._gc_pending.pop(0)
            except IndexError:
                return
            self._append({
                "name": "host/gc", "ph": "X", "ts": (t0 - self._epoch) * 1e6,
                "dur": (t1 - t0) * 1e6, "pid": self._pid(), "tid": tid,
                "args": {"generation": generation},
            })

    def _record(self, sp: Span) -> None:
        if self._gc_pending:  # the collections inside the span, before the span
            self._flush_gc()
        event = {
            "name": sp.name,
            "ph": "X",
            "ts": (sp.t0 - self._epoch) * 1e6,
            "dur": (sp.t1 - sp.t0) * 1e6,
            "pid": self._pid(),
            "tid": self._tid(),
        }
        if sp.args or sp.attributed or sp.t_fence is not None:
            event["args"] = dict(sp.args)
            if sp.t_fence is not None:
                event["args"]["wait_s"] = sp.wait
            if sp.attributed:
                event["args"].update(sp.attributed)
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
                "pid": self._pid(),
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
            "pid": self._pid(),
            "tid": self._track_tid(track) if track else self._tid(),
        }
        if args:
            event["args"] = dict(args)
        self._append(event)

    # -- reading / export ----------------------------------------------

    def events(self) -> List[Dict[str, Any]]:
        self._flush_gc()
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
