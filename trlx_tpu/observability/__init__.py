"""Runtime observability: span tracing, metrics, MFU, and watchdogs.

The reference framework validates performance only empirically on live GPUs;
this repo's hardware-free *compiled* cost net (``trlx_tpu/perf.py``) guards
programs, but nothing observed the *running* system. This subsystem closes
that gap:

- :mod:`tracing` — nestable, rank-aware spans with device fencing
  (``block_until_ready`` at span exit), a Chrome/Perfetto export, a
  ``trlx/<name>`` twin of every span on the ``jax.profiler`` clock, and the
  sink that puts the runtime's traces, lowerings, compiles and cache loads,
  the interpreter's garbage collections and the thread's CPU seconds
  beneath the span that was open;
- :mod:`metrics` — counters/gauges/histograms feeding the existing
  ``Tracker`` stream, plus tokens/sec / samples/sec / **MFU** derived by
  joining fenced step times against XLA ``cost_analysis`` flops of the
  exact compiled programs (``perf.lowered_costs``);
- :mod:`watchdogs` — steady-state recompile detection and device-memory
  gauges with CPU fallback;
- :mod:`profiling` — ``TRLX_TPU_PROFILE=steps:3-5,dir:...`` programmatic
  ``jax.profiler`` windows and per-step ``StepTraceAnnotation``;
- :mod:`distributed` — cross-rank telemetry (``cluster/*`` gauges riding
  the coordinated-preemption allgather), straggler/desync detection, and
  merged multi-rank Perfetto traces on one aligned clock;
- :mod:`flightrec` — a crash flight recorder: bounded ring of recent
  spans, metric updates, and resilience events, dumped as
  ``flightrec.json`` on any exception/NaN-halt/preemption;
- :mod:`dynamics` — on-device fixed-bin distribution sketches of training
  dynamics (log-ratio, KL, advantages, value error, entropy) riding the
  existing stats fetch, summarized into ``dist/*`` percentile gauges;
- :mod:`health` — windowed RL health detectors (KL runaway, entropy
  collapse, clipfrac saturation, value EV collapse, reward flatline,
  generation canary) publishing ``health/*`` gauges and triggering
  bad-batch triage dumps.

:class:`Observability` bundles one instance of each per trainer. See
``docs/OBSERVABILITY.md`` for the span API and metric naming convention.
"""

import os
from dataclasses import dataclass
from typing import Any, Dict, Optional

from trlx_tpu.observability.distributed import (
    ClusterDesyncError,
    ClusterTelemetry,
)
from trlx_tpu.observability.dynamics import DynamicsSummarizer
from trlx_tpu.observability.flightrec import FlightRecorder
from trlx_tpu.observability.health import HealthMonitor
from trlx_tpu.observability.metrics import (
    DEFAULT_PEAK_FLOPS,
    MetricsRegistry,
    ThroughputMeter,
    device_peak_flops,
    mfu,
    train_step_flops,
)
from trlx_tpu.observability.profiling import ProfileWindow, parse_profile_spec
from trlx_tpu.observability import tracing
from trlx_tpu.observability.tracing import Span, Tracer
from trlx_tpu.observability.watchdogs import DeviceMemoryGauge, RecompileWatchdog
from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

__all__ = [
    "ClusterDesyncError",
    "ClusterTelemetry",
    "DEFAULT_PEAK_FLOPS",
    "DeviceMemoryGauge",
    "DynamicsSummarizer",
    "FlightRecorder",
    "HealthMonitor",
    "MetricsRegistry",
    "Observability",
    "ProfileWindow",
    "RecompileWatchdog",
    "Span",
    "ThroughputMeter",
    "Tracer",
    "device_peak_flops",
    "mfu",
    "parse_profile_spec",
    "train_step_flops",
]


@dataclass(slots=True)
class SetupAccount:
    """What set-up was made of (docs/OBSERVABILITY.md "Set-up"):
    ``trlx.train()`` opens it, the trainer notes each phase as it ends and
    :meth:`Observability.freeze_setup` turns it into the ``setup/*`` gauges.
    Slots: a misspelt phase is an AttributeError, not a gauge that reads 0."""

    t_train: Optional[float] = None  # perf_counter where trlx.train() began
    import_s: float = 0.0  # process start to there
    # the sink's totals and its table by program once the runtime is up
    mark: Optional[Dict[str, float]] = None
    programs: Optional[Dict[str, Dict[str, float]]] = None
    init_model_s: float = 0.0  # span setup/init_model
    first_eval_s: Optional[float] = None  # span setup/first_eval, and
    first_eval_end: Optional[float] = None  # where it closed (perf_counter)
    first_collect_s: Optional[float] = None  # the first collection's time/exp

    def begin(self, t_train: float, import_s: float, mark: Dict[str, float],
              programs: Dict[str, Dict[str, float]]) -> None:
        self.t_train, self.import_s, self.mark, self.programs = t_train, import_s, mark, programs


class Observability:
    """Per-trainer bundle: tracer + metrics + watchdogs + profile window.

    Each trainer owns its own instance (no cross-trainer event bleed in a
    process that builds several). ``export()`` writes the span stream next
    to the tracker's stats (``trace.json``), process 0 only — the same
    single-writer gating as the trackers.
    """

    def __init__(self, config: Any = None, trace_dir: Optional[str] = None):
        self.tracer = Tracer()
        self.metrics = MetricsRegistry()
        self.recompile = RecompileWatchdog(self.metrics)
        # no registry mirror: the learn loop merges collect() into its stats
        # directly; mirroring too would double-write every memory/* key and
        # pin stale gauges into future snapshots
        self.memory = DeviceMemoryGauge()
        self.profile = ProfileWindow.from_env(config)
        self.throughput = ThroughputMeter()
        # crash flight recorder (flightrec.py): taps every span and every
        # metric write so the LAST window before a crash survives the crash
        self.flightrec = FlightRecorder(
            capacity=int(os.environ.get("TRLX_TPU_FLIGHTREC_CAP", "512"))
        )
        self.tracer.add_listener(self.flightrec.span_listener)
        self.metrics.add_listener(self.flightrec.metric_listener)
        # cross-rank telemetry (distributed.py): the trainer's step-boundary
        # seam drives beat(); single-process it degenerates to local gauges
        self.cluster = ClusterTelemetry(
            self.tracer, self.metrics, flightrec=self.flightrec
        )
        # training-dynamics sketches + windowed health detectors
        # (dynamics.py / health.py); method knobs read duck-typed so a bare
        # Observability() in tests still builds
        method = getattr(config, "method", None)
        self.dynamics = DynamicsSummarizer(
            cliprange=getattr(method, "cliprange", None)
        )
        self.health = HealthMonitor(
            metrics=self.metrics,
            flightrec=self.flightrec,
            kl_target=getattr(method, "target", None),
        )
        self._warned_dropped = False
        self.setup = SetupAccount()
        # wall-clock construction time: the merge's staleness floor — peer
        # trace files older than this run are a previous incarnation's
        # (same logging dir across a preempt/relaunch) and must not be
        # merged as if they were this run's spans
        import time as _time

        self._t_start_wall = _time.time()
        self._trace_dir = trace_dir or os.environ.get("TRLX_TPU_TRACE_DIR")
        if self._trace_dir is None and config is not None:
            train = getattr(config, "train", None)
            logging_dir = getattr(train, "logging_dir", None)
            checkpoint_dir = getattr(train, "checkpoint_dir", None)
            if logging_dir:
                self._trace_dir = logging_dir
            elif checkpoint_dir:
                self._trace_dir = os.path.join(checkpoint_dir, "logs")

    def span(self, name: str, fence: Any = None, **args: Any):
        return self.tracer.span(name, fence=fence, **args)

    def freeze_setup(self, programs: Optional[Dict[str, Any]] = None) -> None:
        """The second collection begins: set-up is over. Freeze what it was
        made of as ``setup/*`` gauges, which every later step record
        snapshots, and log the table of programs once (``programs``: what the
        job's own executables hold on the device, ``ProgramStore.account()``,
        for the table's last four columns). The phases tile the
        time from ``trlx.train()`` to now: build (``train()`` to the first
        collection, and the eval pipeline and ``prepare_learning`` after
        it), the first evaluation, the first cycle (its collection, and its
        steps up to this collection); what the runtime and the collector
        took of them cuts across. A trainer that ``trlx.train()`` did not
        build, or that resumed past its first evaluation, has no account."""
        s = self.setup
        if s.t_train is None or s.first_eval_end is None:
            return
        now = tracing.mark()
        d = tracing.since(s.mark, now)
        in_train = now["t"] - s.t_train
        first_cycle = (s.first_collect_s or 0.0) + now["t"] - s.first_eval_end
        gauges = {
            "setup/import_s": s.import_s,
            # the residual: what train() spent in neither of the two below
            "setup/build_s": in_train - first_cycle - s.first_eval_s,
            "setup/init_model_s": s.init_model_s,
            "setup/first_eval_s": s.first_eval_s,
            "setup/first_cycle_s": first_cycle,
            "setup/trace_lower_s": d.get("runtime/trace", 0.0) + d.get("runtime/lower", 0.0),
            "setup/compile_s": d.get("runtime/compile", 0.0),
            "setup/cache_load_s": d.get("runtime/cache_load", 0.0),
            "setup/gc_pause_s": d.get("host/gc", 0.0),
            # backend compile events; of them, executables the persistent
            # cache gave and executables compiled and written to it. The rest
            # were compiled and not kept (no cache directory, or a floor on
            # the compile time: trlx.initialize_runtime() sets it to 0)
            "setup/programs": d.get("runtime/programs", 0.0),
            "setup/cache_hits": d.get("runtime/cache_hits", 0.0),
            "setup/cache_misses": d.get("runtime/cache_misses", 0.0),
            # the job's own programs (utils/programs.py): executables loaded
            # without a trace, programs traced and written for the next start
            "setup/store_hits": d.get("runtime/store_hits", 0.0),
            "setup/store_misses": d.get("runtime/store_misses", 0.0),
            "setup/store_load_s": d.get("runtime/store_load", 0.0),
            "setup/store_write_s": d.get("runtime/store_write", 0.0),
            "setup/total_s": s.import_s + in_train,
        }
        gauges["setup/compile_load_s"] = gauges["setup/compile_s"] + gauges["setup/cache_load_s"]
        asked = gauges["setup/store_hits"] + gauges["setup/store_misses"]
        gauges["setup/store_hit_pct"] = 100.0 * gauges["setup/store_hits"] / asked if asked else 0.0
        for name, value in gauges.items():
            self.metrics.set_gauge(name, value)
        logger.info(
            "set-up %.1f s: import %.1f, build %.1f (init_model %.1f), first eval %.1f, "
            "first cycle %.1f; of these the runtime took trace+lower %.1f, compile %.1f, "
            "cache load %.1f (%d programs: %d from the cache, %d written to it, %d "
            "compiled in all; %d loaded without a trace in %.1f) and the collector %.1f\n%s",
            gauges["setup/total_s"], gauges["setup/import_s"], gauges["setup/build_s"],
            gauges["setup/init_model_s"], gauges["setup/first_eval_s"],
            gauges["setup/first_cycle_s"], gauges["setup/trace_lower_s"],
            gauges["setup/compile_s"], gauges["setup/cache_load_s"], gauges["setup/programs"],
            gauges["setup/cache_hits"], gauges["setup/cache_misses"],
            # what a start that found its programs in the cache still compiled:
            # 0 when set-up runs nothing the cache does not keep
            gauges["setup/programs"] - gauges["setup/cache_hits"],
            gauges["setup/store_hits"], gauges["setup/store_load_s"],
            gauges["setup/gc_pause_s"],
            tracing.programs_table(s.programs, held=(programs or {}).get("by_program")),
        )

    def note_dropped_spans(self) -> None:
        """Surface the tracer's silent drop counter as the
        ``obs/spans_dropped`` gauge (warn once when nonzero — a capped
        trace looks complete in the viewer but is lying about the tail)."""
        dropped = self.tracer.dropped
        self.metrics.set_gauge("obs/spans_dropped", float(dropped))
        if dropped and not self._warned_dropped:
            self._warned_dropped = True
            logger.warning(
                "span tracer dropped %d event(s) past its %d-event cap — "
                "the exported trace is missing its tail (raise "
                "Tracer(max_events=...) or export more often); the flight "
                "recorder ring keeps rotating regardless",
                dropped,
                self.tracer.max_events,
            )

    def export(self, directory: Optional[str] = None) -> Dict[str, str]:
        """Write ``trace.json`` (Chrome/Perfetto).

        Multihost: non-zero ranks write ``trace_rank<k>.json`` into the
        shared trace dir (and return {}); process 0 merges every rank's
        events — shifted onto rank 0's clock via the beat-estimated offsets
        — into ONE ``trace.json``. Single-process behavior is unchanged.
        Returns the written paths ({} when there is no directory, no
        events, or this is a non-zero process)."""
        directory = directory or self._trace_dir
        if not directory or not self.tracer.events():
            return {}
        import jax

        from trlx_tpu.observability.distributed import (
            merge_cluster_trace,
            write_rank_trace,
        )

        count = jax.process_count()
        if jax.process_index() != 0:
            if count > 1:
                write_rank_trace(self.tracer, directory, jax.process_index())
            return {}
        if count > 1:
            trace_path = merge_cluster_trace(
                self.tracer,
                directory,
                process_count=count,
                offsets=self.cluster.clock_offsets(),
                # small slack absorbs wall-vs-filesystem clock skew without
                # re-admitting a genuinely previous incarnation's files
                min_mtime=self._t_start_wall - 5.0,
            )
        else:
            trace_path = self.tracer.export_chrome_trace(
                os.path.join(directory, "trace.json")
            )
        return {"trace": trace_path}

    def dump_flight_record(
        self, reason: str, directory: Optional[str] = None
    ) -> Optional[str]:
        """Dump the flight-recorder ring as ``flightrec.json`` (per-rank
        suffixed files off process 0) next to the trace exports. Returns
        the path, or None without a directory — never raises (it runs on
        crash paths)."""
        directory = directory or self._trace_dir
        if not directory:
            return None
        try:
            import jax

            rank = jax.process_index()
        except Exception:  # pragma: no cover - defensive
            rank = 0
        name = "flightrec.json" if rank == 0 else f"flightrec_rank{rank}.json"
        path = self.flightrec.dump(os.path.join(directory, name), reason=reason)
        if path:
            n_records = float(len(self.flightrec.snapshot()))
            self.metrics.inc("flightrec/dumps")
            self.metrics.set_gauge("flightrec/records", n_records)
            logger.warning(f"flight recorder dumped to {path} ({reason})")
        return path
