"""Observability-layer coverage (CPU-only, fast tier).

- spans: nesting in the Chrome/Perfetto export, device fencing, the
  ``trlx/`` twin on the profiler's clock;
- metrics: registry semantics, MFU math against a hand-computed fixture;
- watchdogs: recompile detection on a shape-changing second call, memory
  gauge CPU fallback;
- profiling: ``TRLX_TPU_PROFILE`` spec parsing and window no-ops;
- distributed telemetry: cluster beats over an injected allgather —
  straggler flagging, desync diagnostics, clock offsets, merged traces;
- attribution: what the runtime, the collector and the fence did lands
  beneath the span that was open, once, from worker threads too; the records
  of a toy run carry it, set-up freezes into ``setup/*`` gauges, and a
  planted slow step is named once;
- flight recorder: ring semantics, span/metric taps, dump/reload, and the
  end-to-end NaN-halt dump;
- end-to-end: a tiny PPO smoke run emits the canonical throughput/time keys
  per step and writes a loadable ``trace.json`` with nested
  rollout→generate spans.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.observability import (
    DEFAULT_PEAK_FLOPS,
    ClusterDesyncError,
    ClusterTelemetry,
    FlightRecorder,
    MetricsRegistry,
    Observability,
    ProfileWindow,
    RecompileWatchdog,
    ThroughputMeter,
    Tracer,
    mfu,
    parse_profile_spec,
    train_step_flops,
)
from trlx_tpu.observability.watchdogs import DeviceMemoryGauge


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


class TestTracer:
    def test_nested_spans_nest_in_chrome_export(self):
        tracer = Tracer()
        with tracer.span("rollout"):
            with tracer.span("generate"):
                pass
            with tracer.span("score"):
                pass
        # (an automatic collection inside a span leaves a `host/gc` child)
        events = {e["name"]: e for e in tracer.to_chrome_trace()["traceEvents"]
                  if e["name"] != "host/gc"}
        assert set(events) == {"rollout", "generate", "score"}
        rollout, generate, score = events["rollout"], events["generate"], events["score"]
        # Perfetto nests complete events on one tid by time containment
        assert generate["tid"] == rollout["tid"]
        for child in (generate, score):
            assert child["ts"] >= rollout["ts"]
            assert child["ts"] + child["dur"] <= rollout["ts"] + rollout["dur"] + 1e-3
        # children are disjoint siblings
        assert generate["ts"] + generate["dur"] <= score["ts"] + 1e-3

    def test_fence_blocks_on_device_work(self):
        tracer = Tracer()
        x = jnp.ones((256, 256))
        with tracer.span("matmul") as sp:
            y = jax.jit(lambda a: a @ a)(x)
            sp.fence(y)
        assert sp.duration > 0

    def test_exports_are_loadable(self, tmp_path):
        tracer = Tracer()
        with tracer.span("outer", step=3):
            with tracer.span("inner"):
                pass
        trace_path = tracer.export_chrome_trace(str(tmp_path / "trace.json"))
        trace = json.load(open(trace_path))
        assert {e["name"] for e in trace["traceEvents"]} == {"outer", "inner"}
        assert all(e["ph"] == "X" for e in trace["traceEvents"])
        outer = next(e for e in trace["traceEvents"] if e["name"] == "outer")
        assert outer["args"] == {"step": 3}

    def test_span_lands_on_the_profilers_host_plane(self, tmp_path):
        """While a ``jax.profiler`` session is open every span, worker
        threads' too, is a ``trlx/<name>`` event of the written xplane's
        host plane, closed after the fence, with the span's args as stats."""
        import glob
        import threading

        tracer = Tracer()
        tracer.next_cycle()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with tracer.span("collect/experience"):
                with tracer.span("generate", eval_mode=False) as sp:
                    sp.fence(jax.jit(lambda a: a @ a)(jnp.ones((64, 64))))

            def work():
                with tracer.span("rollout/overlap"):
                    pass

            worker = threading.Thread(target=work)
            worker.start()
            worker.join(timeout=30)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
        found = {}
        for plane in data.planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("trlx/"):
                        assert plane.name.startswith("/host:")
                        found[e.name] = (e, line.name)
        # a collection that ran meanwhile is on the same clock (trlx/host/gc)
        assert set(found) - {"trlx/host/gc"} == {
            "trlx/collect/experience", "trlx/generate", "trlx/rollout/overlap"}
        outer, inner = found["trlx/collect/experience"][0], found["trlx/generate"][0]
        assert outer.start_ns <= inner.start_ns
        assert inner.start_ns + inner.duration_ns <= outer.start_ns + outer.duration_ns
        # the annotation outlives the fenced span it mirrors
        assert inner.duration_ns * 1e-9 >= sp.duration
        assert dict(inner.stats) == {"cycle": 1, "eval_mode": 0}
        # the tracer's own buffer is untouched by the mirror (beside the
        # spans it holds what the sink put beneath them: the jit's compile)
        assert {e["name"] for e in tracer.events() if "/" not in e["name"]
                or e["name"].split("/")[0] not in ("runtime", "host")} == {
            "collect/experience", "generate", "rollout/overlap"}

    def test_span_without_a_profiler_session_is_inert(self, tmp_path):
        tracer = Tracer()
        with tracer.span("collect/experience", step=1) as sp:
            pass
        assert sp.duration >= 0 and [e["name"] for e in tracer.events()] == ["collect/experience"]
        assert list(tmp_path.iterdir()) == []
        with pytest.raises(RuntimeError):  # no session was ever open
            jax.profiler.stop_trace()

    def test_event_buffer_is_bounded(self):
        tracer = Tracer(max_events=5)
        for i in range(10):
            with tracer.span(f"s{i}"):
                pass
        assert len(tracer.events()) == 5
        assert tracer.dropped == 5
        assert tracer.to_chrome_trace()["dropped_events"] == 5

    def test_exception_unwinding_keeps_depth_sane(self):
        tracer = Tracer()
        with pytest.raises(RuntimeError):
            with tracer.span("outer"):
                with tracer.span("inner"):
                    raise RuntimeError("boom")
        with tracer.span("after") as sp:
            pass
        assert sp.depth == 0  # the stack fully unwound


# ---------------------------------------------------------------------------
# attribution: the runtime, the collector and the fence beneath the spans
# (counts and containment only; a time is never compared with a time)
# ---------------------------------------------------------------------------

RECORD_KEYS = {"host/gc_pause_s", "host/gc_gen2", "runtime/retrace_s", "runtime/compile_s",
               "host/cpu_s", "host/invol_switches", "host/major_faults", "host/proc_cpu_s",
               "host/proc_invol_switches"}
COLLECTION_RECORD_KEYS = RECORD_KEYS | {"time/generate_dispatch", "time/generate_wait"}
STEP_RECORD_KEYS = RECORD_KEYS | {"time/train_step_dispatch", "time/train_step_wait"}
SETUP_GAUGES = {
    "setup/import_s", "setup/build_s", "setup/init_model_s", "setup/first_eval_s",
    "setup/first_cycle_s", "setup/trace_lower_s", "setup/compile_s", "setup/cache_load_s",
    "setup/compile_load_s", "setup/gc_pause_s", "setup/programs", "setup/cache_hits",
    "setup/cache_misses", "setup/total_s",
}
SETUP_SPANS = {"setup/runtime_init", "setup/build_trainer", "setup/init_model",
               "setup/tokenizer", "setup/pipelines", "setup/first_eval"}
ATTRIBUTION_LAYER_METRICS = (
    "setup_build_s", "setup_first_eval_s", "setup_first_cycle_s", "setup_trace_lower_s",
    "setup_compile_s", "generate_dispatch_ms", "train_dispatch_ms", "collect_gc_pause_ms",
    "learn_gc_pause_pct", "collect_retrace_ms", "learn_retrace_pct",
)


def _children(tracer, sp, prefix):
    """Events under ``prefix`` recorded on the span's thread inside it."""
    (own,) = [e for e in tracer.events() if e["name"] == sp.name and e["ph"] == "X"]
    return [e for e in tracer.events()
            if e["name"].startswith(prefix) and e["tid"] == own["tid"]
            and own["ts"] <= e["ts"] and e["ts"] + e["dur"] <= own["ts"] + own["dur"] + 1e-3]


@pytest.fixture
def no_automatic_gc():
    import gc

    gc.collect()
    gc.disable()  # an explicit collect() still calls the callbacks
    yield
    gc.enable()


@pytest.mark.parametrize("fenced", [True, False], ids=["fenced", "unfenced"])
def test_dispatch_and_wait_tile_the_span(fenced):
    tracer = Tracer()
    with tracer.span("train_step") as sp:
        y = jax.jit(lambda a: a @ a)(jnp.ones((64, 64)))
        if fenced:
            sp.fence(y)
    assert sp.dispatch + sp.wait == pytest.approx(sp.duration, abs=1e-12)
    (event,) = [e for e in tracer.events() if e["name"] == "train_step"]
    if fenced:
        assert sp.t0 <= sp.t_fence <= sp.t1 and event["args"]["wait_s"] == sp.wait
    else:
        assert sp.wait == 0.0 and sp.t_fence is None and "wait_s" not in event.get("args", {})


@pytest.mark.parametrize("where", ["main", "worker"])
def test_gc_lands_on_the_span_that_was_open(where, no_automatic_gc):
    import gc
    import threading

    tracer = Tracer()
    spans = {}

    def work():
        with tracer.span("obs/before") as spans["before"]:
            pass
        with tracer.span("obs/collecting") as spans["collecting"]:
            gc.collect()
        with tracer.span("obs/after") as spans["after"]:
            pass

    if where == "worker":
        with tracer.span("obs/main_thread") as spans["main"]:
            worker = threading.Thread(target=work)
            worker.start()
            worker.join(timeout=60)
            assert not worker.is_alive()
    else:
        work()
    (child,) = _children(tracer, spans["collecting"], "host/gc")
    assert child["args"] == {"generation": 2}
    assert set(spans["collecting"].attributed) == {"host/gc"}
    for name in set(spans) - {"collecting"}:  # no sibling, and not the other thread
        assert spans[name].attributed is None and _children(tracer, spans[name], "host/gc") == []


@pytest.mark.parametrize("threads", [2, 4])
def test_spans_closing_on_several_threads_drain_the_collections_once(threads):
    """Collections queue up in the callback and the next span to close
    records them; spans close on the main thread and the pipeline worker at
    once, and each collection becomes ONE event whoever drains it, also
    where the interpreter changes threads between a look at the queue and
    the pop (a drain that tests first raises IndexError there)."""
    import threading
    import time

    class SwitchBeforePop(list):
        def pop(self, index=-1):
            time.sleep(0.002)  # the other threads run now
            return super().pop(index)

    tracer = Tracer()
    tracer._gc_pending = SwitchBeforePop()
    rounds, errors = 15, []

    def refill():  # the barrier's action: one thread, the others held
        tracer._gc_pending.append((0.0, 1e-3, 2, 7))

    barrier = threading.Barrier(threads, action=refill)

    def close_spans():
        try:
            for _ in range(rounds):
                barrier.wait(timeout=60)
                with tracer.span("obs/closing"):
                    pass
        except Exception as e:
            errors.append(e)
            barrier.abort()

    workers = [threading.Thread(target=close_spans) for _ in range(threads)]
    for w in workers:
        w.start()
    for w in workers:
        w.join(timeout=120)
    assert errors == [] and not any(w.is_alive() for w in workers)
    planted = [e for e in tracer.events() if e["name"] == "host/gc" and e["tid"] == 7]
    assert len(planted) == rounds
    assert len([e for e in tracer.events() if e["name"] == "obs/closing"]) == rounds * threads


def test_jit_puts_its_trace_lowering_and_compile_under_the_span():
    from trlx_tpu.observability import tracing

    tracer = Tracer()

    def obs_fresh_program(a):
        return a * 3 + 1

    fn = jax.jit(obs_fresh_program)
    before = tracing.mark()
    with tracer.span("obs/first_call") as first:
        fn(jnp.ones((3, 5))).block_until_ready()
    with tracer.span("obs/second_call") as second:
        fn(jnp.ones((3, 5))).block_until_ready()
    mine = [e for e in _children(tracer, first, "runtime/")
            if e["args"]["fun_name"] == "obs_fresh_program"]
    assert sorted(e["name"] for e in mine if e["name"] != "runtime/cache_load") == [
        "runtime/compile", "runtime/lower", "runtime/trace"]
    assert {"runtime/trace", "runtime/lower", "runtime/compile"} <= set(first.attributed)
    assert second.attributed is None and _children(tracer, second, "runtime/") == []
    # the process's totals moved by the same events: one program, by name
    grew = tracing.since(before, tracing.mark())
    assert grew["runtime/programs"] >= 1
    assert tracing.programs()["obs_fresh_program"]["programs"] == 1
    assert "obs_fresh_program" in tracing.recent_programs(first.t0, first.t1)
    assert tracing.recent_programs(second.t0, second.t1) == []


def test_sources_are_registered_once_however_many_tracers():
    import gc

    import jax.monitoring  # noqa: F401
    from jax._src import monitoring
    from trlx_tpu.observability import tracing

    def registered():
        return (gc.callbacks.count(tracing._on_gc),
                monitoring.get_event_time_span_listeners().count(tracing._on_runtime_span),
                monitoring.get_event_duration_listeners().count(tracing._on_runtime_duration),
                monitoring.get_event_listeners().count(tracing._on_runtime_event),
                monitoring.get_scalar_listeners().count(tracing._on_runtime_begin))

    tracers = [Tracer() for _ in range(3)]
    Observability()
    assert registered() == (1, 1, 1, 1, 1)
    tracing.uninstall_sources()
    try:
        assert registered() == (0, 0, 0, 0, 0)
    finally:
        tracing.install_sources()
    assert registered() == (1, 1, 1, 1, 1)
    # every live tracer hears the sink; a dead one is dropped from its list
    tracing.attribute("runtime/trace", 1.0, 2.0, fun_name="obs_nobody")
    assert all([e["name"] for e in t.events()] == ["runtime/trace"] for t in tracers)
    gc.collect()  # tracers that earlier tests of this worker dropped and no collection has reached yet are not this test's
    n_live = len(tracing._live_tracers())
    del tracers[0]
    gc.collect()
    assert len(tracing._live_tracers()) == n_live - 1


class _Recorder:
    def __init__(self):
        self.records = []

    def log(self, stats, step=None):
        self.records.append(dict(stats))

    def finish(self):
        pass


CYCLES = 10  # of two steps each
SLOW_STEP = 15  # the train_step call a sleep is planted in (the eighth cycle's first)


@pytest.fixture(scope="module", params=["ppo", "grpo"])
def attributed_run(request, tmp_path_factory):
    """Ten cycles of two steps at toy widths, one step slowed by a sleep."""
    import time

    import trlx_tpu.trainer.base as base
    import trlx_tpu.trlx as trlx
    from trlx_tpu.data.default_configs import default_grpo_config, default_ppo_config

    tmp_path = tmp_path_factory.mktemp("attributed")
    grpo = request.param == "grpo"
    config = (default_grpo_config if grpo else default_ppo_config)().evolve(
        train=dict(
            seq_length=24, batch_size=8, total_steps=2 * CYCLES, eval_interval=100,
            checkpoint_interval=100, epochs=CYCLES, save_best=False, tracker=None,
            checkpoint_dir=str(tmp_path / "ckpts"), logging_dir=str(tmp_path / "logs"),
        ),
        model=dict(model_path="builtin:gpt2-test", num_layers_unfrozen=1),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        method=dict(
            num_rollouts=16, chunk_size=8, ppo_epochs=1,
            gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0, do_sample=True),
            **(dict(group_size=4) if grpo else {}),
        ),
    )
    recorder = _Recorder()

    def hook(trainer):
        trainer.tracker = recorder
        train_step, calls = trainer.train_step, []

        def slowed(batch, **kw):
            calls.append(time.perf_counter())
            if len(calls) == SLOW_STEP:
                # thirty of the cycles this machine has run since the compiles
                # (from a call to the call two steps on is one cycle); seven
                # cycles of history make the median this one is held against
                # deaf to a burst of load
                windows = sorted(calls[i] - calls[i - 2] for i in range(4, SLOW_STEP, 2))
                time.sleep(min(max(3.0, 30 * windows[len(windows) // 2]), 40.0))
            return train_step(batch, **kw)

        trainer.train_step = slowed

    # a CPU under six test workers is no steady machine: only the planted
    # sleep (thirty cycles long, on steps of some 10 ms) may stand out
    ratio, base.SLOW_INTERVAL_RATIO = base.SLOW_INTERVAL_RATIO, 20.0
    # the MFU gauge lowers the train step once more on a thread of its own
    # after the first step, which the second step's record would show
    mfu_env, os.environ["TRLX_TPU_MFU"] = os.environ.get("TRLX_TPU_MFU"), "0"
    # room in the ring for everything after the slowed step: the run's last
    # save executes some eighty operations one by one, each a trace, a
    # lowering, a compile and (since every program is kept) a cache event
    cap_env, os.environ["TRLX_TPU_FLIGHTREC_CAP"] = os.environ.get("TRLX_TPU_FLIGHTREC_CAP"), "4096"
    try:
        trainer = trlx.train(
            reward_fn=lambda samples, prompts, outputs, **kw: [float(len(o)) for o in outputs],
            prompts=["ab", "cd", "ef", "gh", "ij", "kl", "mn", "op"], config=config,
            init_trainer_hook=hook)
    finally:
        base.SLOW_INTERVAL_RATIO = ratio
        for name, old in (("TRLX_TPU_MFU", mfu_env), ("TRLX_TPU_FLIGHTREC_CAP", cap_env)):
            if old is None:
                del os.environ[name]
            else:
                os.environ[name] = old
    return {"trainer": trainer, "records": recorder.records,
            "events": trainer.obs.tracer.events()}


def _steps(run):
    return [r for r in run["records"] if "time/train_step" in r]


def test_every_record_carries_its_attribution(attributed_run):
    collections = [r for r in attributed_run["records"] if "time/exp" in r]
    steps = _steps(attributed_run)
    assert len(collections) == CYCLES and len(steps) == 2 * CYCLES
    for r in collections:
        assert COLLECTION_RECORD_KEYS <= set(r), sorted(COLLECTION_RECORD_KEYS - set(r))
        assert r["time/generate_dispatch"] + r["time/generate_wait"] == pytest.approx(
            r["time/generate"], rel=1e-9)
    for r in steps:
        assert STEP_RECORD_KEYS <= set(r), sorted(STEP_RECORD_KEYS - set(r))
        # the launch and the landing's fence; between them the host lands the
        # step before (a step launched ahead) or launches the one after
        assert r["time/train_step_dispatch"] > 0.0
        assert 0.0 <= r["time/train_step_wait"] <= r["time/train_step"]
    # one width, one program: only the first step of the shape traces
    assert steps[0]["runtime/retrace_s"] > 0 and steps[0]["runtime/compile_s"] > 0
    assert [r["runtime/retrace_s"] for r in steps[1:]] == [0.0] * (2 * CYCLES - 1)
    assert [r["runtime/compile_s"] for r in steps[1:]] == [0.0] * (2 * CYCLES - 1)
    # after the first cycle a collection compiles nothing either, and
    # retraces nothing: the walk over the cache's shapes that every
    # generate() call used to trace (base.py::_note_dense_kv_gauge; PERF.md
    # section 6, PR 35) is made once a shape since PR 36
    assert collections[0]["runtime/compile_s"] > 0 and collections[0]["runtime/retrace_s"] > 0
    assert [r["runtime/compile_s"] for r in collections[1:]] == [0.0] * (CYCLES - 1)
    assert [r["runtime/retrace_s"] for r in collections[1:]] == [0.0] * (CYCLES - 1)
    events = attributed_run["events"]
    later = [c for c in events if c["name"] == "collect/experience"][1:]
    inside = [e for e in events if e["name"].startswith("runtime/") and any(
        c["ts"] <= e["ts"] and e["ts"] + e["dur"] <= c["ts"] + c["dur"] + 1e-3 for c in later)]
    assert not inside, {(e["name"], e["args"]["fun_name"]) for e in inside}


def test_setup_is_under_spans_and_frozen_at_the_second_collection(attributed_run):
    names = {e["name"] for e in attributed_run["events"]}
    assert SETUP_SPANS <= names, sorted(SETUP_SPANS - names)
    steps = _steps(attributed_run)
    for r in steps[:2]:  # the first cycle is set-up itself
        assert not SETUP_GAUGES & set(r)
    frozen = {k: steps[2][k] for k in SETUP_GAUGES}
    for r in steps[2:]:
        assert {k: r[k] for k in SETUP_GAUGES} == frozen
    total = frozen["setup/total_s"]
    phases = ("setup/import_s", "setup/build_s", "setup/first_eval_s", "setup/first_cycle_s")
    counts = {"setup/programs", "setup/cache_hits", "setup/cache_misses"}  # not seconds
    assert all(0 <= frozen[k] <= total for k in SETUP_GAUGES - counts)
    assert sum(frozen[k] for k in phases) == pytest.approx(total, rel=1e-9)  # they tile it
    assert frozen["setup/init_model_s"] <= frozen["setup/build_s"]
    assert frozen["setup/compile_load_s"] == frozen["setup/compile_s"] + frozen["setup/cache_load_s"]
    assert frozen["setup/programs"] >= 3  # generate, score, the train step
    # of them, executables the persistent cache gave or took; the rest
    # compiled too fast to be kept
    assert 0 <= frozen["setup/cache_hits"] + frozen["setup/cache_misses"] <= frozen["setup/programs"]
    # the runtime's work sits beneath the spans that caused it
    for span, program in (("generate", "rollout_generate"), ("train_step", "train_step")):
        (first, *_) = [e for e in attributed_run["events"] if e["name"] == span]
        inside = [e for e in attributed_run["events"] if e["name"].startswith("runtime/")
                  and e["tid"] == first["tid"] and first["ts"] <= e["ts"]
                  and e["ts"] + e["dur"] <= first["ts"] + first["dur"] + 1e-3]
        assert {e["name"] for e in inside if e["args"]["fun_name"] == program} >= {
            "runtime/trace", "runtime/lower", "runtime/compile"}


def test_a_planted_sleep_is_named_once(attributed_run, trlx_log_records):
    steps = _steps(attributed_run)
    counts = [r.get("host/slow_steps", 0.0) for r in steps]
    assert counts == [0.0] * (SLOW_STEP - 1) + [1.0] * (len(steps) - SLOW_STEP + 1)
    ring = [r["data"] for r in attributed_run["trainer"].obs.flightrec.snapshot()
            if r["kind"] == "slow_interval"]
    (step,) = [d for d in ring if d["kind"] == "step"]
    # asleep in train_step before the fence: dispatch, and off the CPU
    assert step["verdict"] == "thread not running"
    assert step["line"].startswith(f"step {SLOW_STEP - 1}: ") and "train_step dispatch +" in step["line"]
    assert f"{int(step['host/major_faults'])} major faults: thread not running" in step["line"]
    # the cycle that held it is named too, when the next collection closes it
    (cycle,) = [d for d in ring if d["kind"] == "cycle"]
    assert cycle["line"].startswith(f"cycle {(SLOW_STEP + 1) // 2}: ")
    assert steps[-1]["host/slow_cycles"] == 1.0


@pytest.mark.parametrize("name", ATTRIBUTION_LAYER_METRICS)
def test_attribution_layer_metric_reads_what_the_program_emits(attributed_run, name):
    """The eleven per-layer metrics of PR 35 are data over reducers the
    benchmark already had, each over one key the records carry."""
    from types import SimpleNamespace

    from chipbench import layers

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "chipbench", "layer_metrics", f"{name}.json")) as f:
        spec = json.load(f)
    declared = {m["name"]: m for m in layers.job.load_benchmark()["per_layer"]}[name]
    assert "workloads" not in declared
    assert {k: spec[k] for k in ("name", "unit", "better", "source", "layer", "moves")} == declared
    assert spec["reducer"] in ("stat_median", "stat_mean", "stat_share")
    # cycles as the harness keeps them, from the second on (the window)
    cycles, now = [], 0.0
    for r in attributed_run["records"]:
        if "time/exp" in r:
            cycles.append({"collection": r, "steps": [], "start": now, "end": now + 1.0})
            now += 1.0
        elif "time/train_step" in r:
            cycles[-1]["steps"].append(r)
    value = layers.reduce_one(spec, SimpleNamespace(cycles=cycles[1:]), None, None, 1)
    assert value is not None and value >= 0.0
    if name in ("learn_retrace_pct", "collect_retrace_ms"):
        assert value == 0.0  # programs_compiled's inside twins: any reading is a retrace
    # a program without the key (the parent of PR 35) reports nothing
    bare = [{"collection": {"time/exp": 1.0}, "steps": [{"time/train_step": 1.0}],
             "start": 0.0, "end": 1.0}]
    assert layers.reduce_one(spec, SimpleNamespace(cycles=bare), None, None, 1) is None


# ---------------------------------------------------------------------------
# metrics / MFU
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_registry_counters_gauges_histograms(self):
        reg = MetricsRegistry()
        reg.inc("recompile/train_step")
        reg.inc("recompile/train_step", 2)
        reg.set_gauge("memory/host_rss_bytes", 123.0)
        reg.observe("time/host_block", 0.1)
        reg.observe("time/host_block", 0.3)
        snap = reg.snapshot()
        assert snap["recompile/train_step"] == 3
        assert snap["memory/host_rss_bytes"] == 123.0
        assert snap["time/host_block_mean"] == pytest.approx(0.2)
        assert snap["time/host_block_max"] == pytest.approx(0.3)
        assert snap["time/host_block_count"] == 2
        # histograms reset per snapshot; counters/gauges persist
        snap2 = reg.snapshot()
        assert "time/host_block_mean" not in snap2
        assert snap2["recompile/train_step"] == 3

    def test_mfu_hand_computed_fixture(self):
        # 1e12 flops on a device with 2e12 peak over 1s → 50% MFU
        assert mfu(1e12, 1.0, 2e12) == pytest.approx(0.5)
        # twice the time → half the utilization
        assert mfu(1e12, 2.0, 2e12) == pytest.approx(0.25)
        # degenerate inputs never divide by zero
        assert mfu(1e12, 0.0, 2e12) == 0.0
        assert mfu(1e12, 1.0, 0.0) == 0.0

    def test_throughput_meter_cross_check(self, monkeypatch):
        monkeypatch.delenv("TRLX_TPU_PEAK_FLOPS", raising=False)
        meter = ThroughputMeter(peak_flops_per_device=2e12)
        stats = meter.step_stats(
            0.5, tokens=1000, samples=8, flops_per_device=5e11
        )
        assert stats["throughput/tokens_per_sec"] == pytest.approx(2000.0)
        assert stats["throughput/samples_per_sec"] == pytest.approx(16.0)
        # 5e11 flops / 0.5 s = 1e12 flop/s against 2e12 peak → 0.5
        assert stats["throughput/mfu"] == pytest.approx(0.5)
        assert stats["throughput/flops_per_sec_per_device"] == pytest.approx(1e12)
        meter.step_stats(0.5, tokens=3000, samples=8)
        summary = meter.summary()
        assert summary["throughput/tokens_per_sec_avg"] == pytest.approx(4000.0)

    def test_peak_env_override(self, monkeypatch):
        monkeypatch.setenv("TRLX_TPU_PEAK_FLOPS", "4e12")
        meter = ThroughputMeter()
        assert meter.peak == pytest.approx(4e12)

    def test_train_step_flops_of_compiled_program(self, monkeypatch):
        # tests/test_learn_ahead.py leaves TRLX_TPU_MFU=0 in the worker's environment (failed the whole run of PR 57 once)
        monkeypatch.delenv("TRLX_TPU_MFU", raising=False)
        fn = jax.jit(lambda s, b: (s @ b).sum())
        s = jnp.ones((64, 64), jnp.float32)
        b = jnp.ones((64, 64), jnp.float32)
        flops = train_step_flops(fn, s, b)
        assert flops is not None
        # a 64^3 matmul is ~2*64^3 = 524k flops; cost_analysis must be in
        # that ballpark (fusion may fold the sum, hence the loose band)
        assert 2 * 64**3 * 0.5 < flops < 2 * 64**3 * 4

    def test_train_step_flops_disabled_by_env(self, monkeypatch):
        monkeypatch.setenv("TRLX_TPU_MFU", "0")
        fn = jax.jit(lambda s, b: s + b)
        assert train_step_flops(fn, jnp.ones(2), jnp.ones(2)) is None


# ---------------------------------------------------------------------------
# watchdogs
# ---------------------------------------------------------------------------


class TestRecompileWatchdog:
    def test_fires_on_shape_changing_second_call(self, trlx_log_records):
        reg = MetricsRegistry()
        dog = RecompileWatchdog(reg)
        fn = jax.jit(lambda x: x * 2)

        fn(jnp.ones((4,)))
        assert dog.observe("train_step", fn) == 0  # warmup compile: silent
        assert not trlx_log_records

        fn(jnp.ones((8,)))  # shape drift → retrace
        excess = dog.observe("train_step", fn)
        assert excess == 1
        assert reg.counter("recompile/train_step") == 1
        assert any("retraced" in r.getMessage() for r in trlx_log_records)

        # steady state after the drift: no further warnings
        del trlx_log_records[:]
        fn(jnp.ones((8,)))
        dog.observe("train_step", fn)
        assert not trlx_log_records

    def test_signature_fallback_when_cache_size_unavailable(self, trlx_log_records):
        reg = MetricsRegistry()
        dog = RecompileWatchdog(reg)
        fn = lambda x: x  # noqa: E731 — no _cache_size attr

        dog.observe("score", fn, args=(np.ones((4,)),))
        excess = dog.observe("score", fn, args=(np.ones((8,)),))
        assert excess == 1
        assert reg.counter("recompile/score") == 1
        assert any("retraced" in r.getMessage() for r in trlx_log_records)

    def test_two_programs_under_one_name_do_not_cross_trigger(
        self, trlx_log_records
    ):
        """The first compile of a *second* jitted fn sharing a logical name
        (eval-config vs experience-config generate) is warmup, not a
        retrace."""
        reg = MetricsRegistry()
        dog = RecompileWatchdog(reg)
        fn_a = jax.jit(lambda x: x * 2)
        fn_b = jax.jit(lambda x: x * 3)
        fn_a(jnp.ones((4,)))
        dog.observe("generate", fn_a)
        fn_b(jnp.ones((4,)))
        dog.observe("generate", fn_b)  # fn_b's own first compile: silent
        assert reg.counter("recompile/generate") == 0
        assert not trlx_log_records
        fn_b(jnp.ones((16,)))  # fn_b's own retrace: fires
        assert dog.observe("generate", fn_b) == 1
        assert reg.counter("recompile/generate") == 1
        assert dog.excess_compiles("generate") == 1

    @pytest.mark.parametrize("tracked_by", ["cache_size", "signature"])
    def test_first_compile_of_a_planned_shape_is_expected(
        self, trlx_log_records, tracked_by
    ):
        """A pad policy feeds one program a fixed set of shapes (the PPO
        learner's ladder of widths): the first compile of each shape it names
        is warm-up; a second compile at a shape already seen, and any compile
        at a shape it did not plan, still count."""
        reg = MetricsRegistry()
        dog = RecompileWatchdog(reg)
        if tracked_by == "cache_size":
            fn = jax.jit(lambda x: x * 2)
            call = lambda x: (fn(x), dog.observe("train_step", fn, planned=x.shape))[1]  # noqa: E731
        else:
            fn = lambda x: x  # noqa: E731 — no _cache_size attr
            call = lambda x: dog.observe("train_step", fn, args=(x,), planned=x.shape)  # noqa: E731
        for width in (256, 384, 640, 1024, 384, 256):
            assert call(jnp.ones((width,))) == 0
        assert reg.counter("recompile/train_step") == 0
        assert not trlx_log_records
        # the same planned shape compiles again (a dtype drift): counted
        assert call(jnp.ones((384,), jnp.int32)) == 1
        assert reg.counter("recompile/train_step") == 1
        assert any("retraced" in r.getMessage() for r in trlx_log_records)
        # a shape outside the plan: counted
        if tracked_by == "cache_size":
            fn(jnp.ones((100,)))
            assert dog.observe("train_step", fn) == 2
        else:
            assert dog.observe("train_step", fn, args=(jnp.ones((100,)),)) == 2
        assert reg.counter("recompile/train_step") == 2
        assert dog.excess_compiles("train_step") == 2

    def test_warning_flood_is_capped(self, trlx_log_records):
        dog = RecompileWatchdog(max_warnings=2)
        fn = lambda x: x  # noqa: E731
        for i in range(10):
            dog.observe("generate", fn, args=(np.ones((i + 1,)),))
        warnings = [r for r in trlx_log_records if "retraced" in r.getMessage()]
        assert len(warnings) == 2


class TestDeviceMemoryGauge:
    def test_cpu_fallback_reports_host_rss(self):
        reg = MetricsRegistry()
        gauge = DeviceMemoryGauge(reg)
        out = gauge.collect()
        # CPU devices expose no memory_stats(); host RSS always lands
        assert out["memory/host_rss_bytes"] > 0
        assert reg.snapshot()["memory/host_rss_bytes"] == out["memory/host_rss_bytes"]


# ---------------------------------------------------------------------------
# profiling windows
# ---------------------------------------------------------------------------


class TestProfileWindow:
    def test_spec_parsing(self):
        assert parse_profile_spec("steps:3-5,dir:/tmp/x") == (3, 5, "/tmp/x")
        assert parse_profile_spec("steps:7") == (7, 7, "/tmp/trlx_tpu_profile")

    @pytest.mark.parametrize(
        "spec", ["dir:/tmp/x", "steps:5-3", "bogus:1,steps:1-2"]
    )
    def test_malformed_specs_raise(self, spec):
        with pytest.raises(ValueError):
            parse_profile_spec(spec)

    def test_env_spec_builds_window(self, monkeypatch):
        monkeypatch.setenv("TRLX_TPU_PROFILE", "steps:2-4,dir:/tmp/prof")
        window = ProfileWindow.from_env()
        assert (window.start, window.stop_step, window.directory) == (2, 4, "/tmp/prof")

    def test_malformed_env_spec_is_ignored(self, monkeypatch, trlx_log_records):
        monkeypatch.setenv("TRLX_TPU_PROFILE", "steps:banana")
        window = ProfileWindow.from_env()
        assert not window.enabled
        assert any("malformed" in r.getMessage() for r in trlx_log_records)

    def test_disabled_window_is_noop(self):
        window = ProfileWindow.disabled()
        window.on_step_start(0)
        window.on_step_end(0)
        window.stop()
        assert not window.active
        with window.step_annotation("train", 0):
            pass  # nullcontext


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_is_bounded_and_counts_all_records(self):
        rec = FlightRecorder(capacity=4)
        for i in range(10):
            rec.record("step", {"iter": i})
        snap = rec.snapshot()
        assert len(snap) == 4
        assert [r["data"]["iter"] for r in snap] == [6, 7, 8, 9]
        assert rec.recorded == 10

    def test_span_tap_outlives_the_tracer_cap(self):
        """The recorder ring must keep rotating after the tracer's bounded
        buffer starts dropping — that tail is exactly the crash window."""
        tracer = Tracer(max_events=3)
        rec = FlightRecorder(capacity=5)
        tracer.add_listener(rec.span_listener)
        for i in range(10):
            with tracer.span(f"obs/s{i}"):
                pass
        assert len(tracer.events()) == 3 and tracer.dropped == 7
        names = [r["data"]["name"] for r in rec.snapshot()]
        assert names == ["obs/s5", "obs/s6", "obs/s7", "obs/s8", "obs/s9"]

    def test_metric_tap_records_writes(self):
        reg = MetricsRegistry()
        rec = FlightRecorder()
        reg.add_listener(rec.metric_listener)
        reg.inc("resilience/nonfinite_updates")
        reg.set_gauge("cluster/step_skew_s", 0.25)
        kinds = [(r["data"]["op"], r["data"]["name"]) for r in rec.snapshot()]
        assert ("inc", "resilience/nonfinite_updates") in kinds
        assert ("gauge", "cluster/step_skew_s") in kinds

    def test_dump_reload_and_jsonable_coercion(self, tmp_path):
        rec = FlightRecorder(capacity=8)
        rec.record("engine_stats", {"arr": np.arange(6).reshape(2, 3),
                                    "scalar": np.float32(1.5)})
        path = rec.dump(str(tmp_path / "flightrec.json"), reason="test crash")
        doc = json.load(open(path))
        assert doc["reason"] == "test crash"
        assert doc["records"][0]["kind"] == "engine_stats"
        assert doc["records"][0]["data"]["scalar"] == pytest.approx(1.5)
        assert "shape=(2, 3)" in doc["records"][0]["data"]["arr"]
        # a second dump is a fresh atomic write, numbered
        path2 = rec.dump(str(tmp_path / "flightrec.json"), reason="again")
        assert json.load(open(path2))["dump_number"] == 2

    def test_observability_dump_counts_and_gauges(self, tmp_path):
        obs = Observability(trace_dir=str(tmp_path))
        with obs.span("obs/unit"):
            pass
        path = obs.dump_flight_record(reason="unit")
        assert path and path.endswith("flightrec.json")
        snap = obs.metrics.snapshot()
        assert snap["flightrec/dumps"] == 1
        assert snap["flightrec/records"] >= 1
        kinds = {r["kind"] for r in json.load(open(path))["records"]}
        assert "span" in kinds


def test_spans_dropped_gauge_warns_once(trlx_log_records):
    obs = Observability()
    obs.tracer.max_events = 2
    for i in range(5):
        with obs.span(f"obs/s{i}"):
            pass
    obs.note_dropped_spans()
    obs.note_dropped_spans()
    assert obs.metrics.snapshot()["obs/spans_dropped"] == 3
    warnings = [r for r in trlx_log_records if "dropped" in r.getMessage()]
    assert len(warnings) == 1  # warn-once
    # zero drops: gauge present, no warning
    obs2 = Observability()
    obs2.note_dropped_spans()
    assert obs2.metrics.snapshot()["obs/spans_dropped"] == 0.0


# ---------------------------------------------------------------------------
# distributed telemetry (cluster beats, stragglers, merged traces)
# ---------------------------------------------------------------------------


def _fake_cluster(tracer, metrics, peers, **kwargs):
    """A ClusterTelemetry whose allgather stacks the local vector with
    fabricated peer rows — 2-rank semantics without a second process.
    ``peers`` is a list of dicts overriding PACK_FIELDS per fake rank."""
    from trlx_tpu.observability.distributed import PACK_FIELDS

    def allgather(vec):
        rows = [vec]
        for peer in peers:
            row = np.array(vec, np.float32)
            for field, value in peer.items():
                row[PACK_FIELDS.index(field)] = value
            rows.append(row)
        return np.stack(rows)

    return ClusterTelemetry(
        tracer, metrics, allgather=allgather, enabled=True, **kwargs
    )


class TestClusterTelemetry:
    def test_single_process_beat_publishes_local_gauges(self):
        reg = MetricsRegistry()
        cluster = ClusterTelemetry(Tracer(), reg, enabled=True)
        cluster.note_step(0.2, tokens_per_sec=100.0, device_bytes=1e6)
        assert cluster.beat(False, step=0) is False
        snap = reg.snapshot()
        assert snap["cluster/size"] == 1.0
        assert snap["cluster/step_time_max_s"] == pytest.approx(0.2)
        assert snap["cluster/step_skew_s"] == 0.0
        assert snap["cluster/straggler_rank"] == -1.0

    def test_straggler_flagged_after_patience_beats(self, trlx_log_records):
        reg = MetricsRegistry()
        cluster = _fake_cluster(
            Tracer(), reg, peers=[{"step_time_s": 0.9}], straggler_patience=2
        )
        cluster.note_step(0.1)
        cluster.beat(False, step=0)
        snap = reg.snapshot()
        assert snap["cluster/straggler_rank"] == -1.0  # one beat: not yet
        assert snap["cluster/step_skew_s"] == pytest.approx(0.8)
        cluster.note_step(0.1)
        cluster.beat(False, step=1)
        snap = reg.snapshot()
        assert snap["cluster/straggler_rank"] == 1.0
        assert any("straggler" in r.getMessage() for r in trlx_log_records)
        # recovery clears the flag
        cluster = _fake_cluster(Tracer(), reg, peers=[{}], straggler_patience=2)
        cluster.note_step(0.1)
        cluster.beat(False, step=0)
        cluster.beat(False, step=1)
        assert reg.snapshot()["cluster/straggler_rank"] == -1.0

    def test_desync_raises_hard_diagnostic(self):
        cluster = _fake_cluster(Tracer(), MetricsRegistry(), peers=[{"step": 7}])
        cluster.note_step(0.1)
        with pytest.raises(ClusterDesyncError, match="rank 1: step 7"):
            cluster.beat(False, step=3)

    def test_preemption_flag_rides_the_beat(self):
        reg = MetricsRegistry()
        assert _fake_cluster(Tracer(), reg, peers=[{"preempt": 1.0}]).beat(
            False, step=0
        ) is True
        assert _fake_cluster(Tracer(), reg, peers=[{}]).beat(True, step=0) is True
        assert _fake_cluster(Tracer(), reg, peers=[{}]).beat(False, step=0) is False

    def test_clock_offsets_estimated_from_beats(self):
        # the fake peer's clock reads 2.5s behind rank 0's at every barrier
        cluster = _fake_cluster(
            Tracer(), MetricsRegistry(), peers=[{"clock_s": 0.0}]
        )
        for step in range(3):
            cluster.beat(False, step=step)
        offsets = cluster.clock_offsets()
        assert offsets[0] == pytest.approx(0.0)
        assert offsets[1] > 0  # rank 1's clock_s=0 → offset = rank0's clock

    def test_disabled_beat_is_a_noop(self):
        reg = MetricsRegistry()
        cluster = ClusterTelemetry(Tracer(), reg, enabled=False)
        assert cluster.beat(True, step=0) is True
        assert "cluster/size" not in reg.snapshot()


class TestMergedTrace:
    def _rank_doc(self, events):
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def test_merges_rank_files_on_rank_zero_clock(self, tmp_path):
        from trlx_tpu.observability.distributed import merge_cluster_trace

        tracer = Tracer()
        with tracer.span("train_step"):
            pass
        peer_events = [
            {"name": "train_step", "ph": "X", "ts": 100.0, "dur": 50.0,
             "pid": 1, "tid": 7},
        ]
        (tmp_path / "trace_rank1.json").write_text(
            json.dumps(self._rank_doc(peer_events))
        )
        out = merge_cluster_trace(
            tracer, str(tmp_path), process_count=2, offsets={1: 0.5},
            timeout_s=1.0,
        )
        doc = json.load(open(out))
        events = doc["traceEvents"]
        pids = {e["pid"] for e in events if e["ph"] == "X"}
        assert pids == {0, 1}
        merged_peer = next(
            e for e in events if e["ph"] == "X" and e["pid"] == 1
        )
        assert merged_peer["ts"] == pytest.approx(100.0 + 0.5e6)
        labels = {
            e["pid"]: e["args"]["name"]
            for e in events
            if e["name"] == "process_name"
        }
        assert labels == {0: "rank 0", 1: "rank 1"}
        assert doc["clock_offsets_s"] == {"1": 0.5}

    def test_stale_peer_file_is_not_merged(self, tmp_path, trlx_log_records):
        # a relaunched run sharing the logging dir must not merge the
        # PREVIOUS incarnation's peer trace as this run's spans
        from trlx_tpu.observability.distributed import merge_cluster_trace

        tracer = Tracer()
        with tracer.span("train_step"):
            pass
        path = tmp_path / "trace_rank1.json"
        path.write_text(
            json.dumps(
                self._rank_doc(
                    [{"name": "train_step", "ph": "X", "ts": 1.0,
                      "dur": 1.0, "pid": 1, "tid": 7}]
                )
            )
        )
        out = merge_cluster_trace(
            tracer,
            str(tmp_path),
            process_count=2,
            timeout_s=0.0,
            min_mtime=os.path.getmtime(path) + 10.0,
        )
        doc = json.load(open(out))
        assert doc["missing_ranks"] == [1]
        assert {e["pid"] for e in doc["traceEvents"] if e["ph"] == "X"} == {0}

    def test_missing_rank_is_bounded_not_fatal(self, tmp_path, trlx_log_records):
        from trlx_tpu.observability.distributed import merge_cluster_trace

        tracer = Tracer()
        with tracer.span("train_step"):
            pass
        out = merge_cluster_trace(
            tracer, str(tmp_path), process_count=2, timeout_s=0.0
        )
        doc = json.load(open(out))
        assert doc["missing_ranks"] == [1]
        assert {e["pid"] for e in doc["traceEvents"] if e["ph"] == "X"} == {0}
        assert any(
            "no fresh trace from rank 1" in r.getMessage()
            for r in trlx_log_records
        )


# ---------------------------------------------------------------------------
# end-to-end PPO smoke (the acceptance-criteria run)
# ---------------------------------------------------------------------------


def test_ppo_smoke_emits_throughput_and_trace(tmp_path, monkeypatch):
    import trlx_tpu.trlx as trlx

    # the MFU gauge is what this test reads: a switch another test of the worker left in the environment
    # (tests/test_learn_ahead.py's module fixtures did until PR 60) must not turn it off
    monkeypatch.delenv("TRLX_TPU_MFU", raising=False)
    from trlx_tpu.data.default_configs import default_ppo_config

    config = default_ppo_config().evolve(
        train=dict(
            seq_length=24,
            batch_size=8,
            total_steps=2,
            eval_interval=10,
            checkpoint_interval=10,
            epochs=1,
            save_best=False,
            checkpoint_dir=str(tmp_path / "ckpts"),
            logging_dir=str(tmp_path / "logs"),
            tracker="jsonl",
        ),
        model=dict(model_path="builtin:gpt2-test", num_layers_unfrozen=1),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        method=dict(
            num_rollouts=8,
            chunk_size=8,
            ppo_epochs=2,
            gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0, do_sample=True),
        ),
    )

    def reward_fn(samples, prompts, outputs, **kwargs):
        return [float(len(o)) for o in outputs]

    prompts = ["ab", "cd", "ef", "gh", "ij", "kl", "mn", "op"]
    trlx.train(reward_fn=reward_fn, prompts=prompts, config=config)

    records = [
        json.loads(l) for l in open(tmp_path / "logs" / "stats.jsonl")
    ]
    keys = set().union(*(set(r) for r in records))
    # canonical per-step throughput/time keys (acceptance criteria)
    for key in (
        "throughput/tokens_per_sec",
        "throughput/samples_per_sec",
        "throughput/mfu",
        "time/rollout",
        "time/rollout_host",
        "time/score",
        "time/train_step",
        "time/step",
        "throughput/rollout_overlap_frac",
        "memory/host_rss_bytes",
    ):
        assert key in keys, f"stats stream is missing {key}: {sorted(keys)}"
    mfu_vals = [r["throughput/mfu"] for r in records if "throughput/mfu" in r]
    assert all(0 < v < 10 for v in mfu_vals)  # nominal CPU peak: index, not %
    # steady state must be retrace-free: the watchdog counter only appears
    # once a warm program recompiles (regression guard for the step-2
    # output-sharding retrace the watchdog originally caught)
    assert "recompile/train_step" not in keys

    # Chrome trace: loadable, with generate nested inside rollout
    trace = json.load(open(tmp_path / "logs" / "trace.json"))
    events = trace["traceEvents"]
    rollouts = [e for e in events if e["name"] == "rollout"]
    generates = [e for e in events if e["name"] == "generate"]
    assert rollouts and generates
    nested = [
        (g, r)
        for g in generates
        for r in rollouts
        if r["ts"] <= g["ts"] and g["ts"] + g["dur"] <= r["ts"] + r["dur"] + 1e-3
    ]
    assert nested, "no generate span nested inside a rollout span"
    # distributed-telemetry gauges ride the stream even single-process
    # (skew degenerates to 0.0 over one rank) with the drop gauge beside
    assert "cluster/step_skew_s" in keys
    assert "cluster/straggler_rank" in keys
    assert "obs/spans_dropped" in keys


def _obs_ppo_config(tmp_path, **train_overrides):
    from trlx_tpu.data.default_configs import default_ppo_config

    train = dict(
        seq_length=24,
        batch_size=8,
        total_steps=2,
        eval_interval=10,
        checkpoint_interval=10,
        epochs=1,
        save_best=False,
        checkpoint_dir=str(tmp_path / "ckpts"),
        logging_dir=str(tmp_path / "logs"),
        tracker="jsonl",
    )
    train.update(train_overrides)
    return default_ppo_config().evolve(
        train=train,
        model=dict(model_path="builtin:gpt2-test", num_layers_unfrozen=1),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        method=dict(
            num_rollouts=8,
            chunk_size=8,
            ppo_epochs=2,
            gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0, do_sample=True),
        ),
    )


def _run_obs_ppo(config):
    import trlx_tpu.trlx as trlx

    def reward_fn(samples, prompts, outputs, **kwargs):
        return [float(len(o)) for o in outputs]

    prompts = ["ab", "cd", "ef", "gh", "ij", "kl", "mn", "op"]
    return trlx.train(reward_fn=reward_fn, prompts=prompts, config=config)


def test_flightrec_dumps_on_nan_halt(tmp_path):
    """Acceptance: an injected NaN-halt crash leaves a ``flightrec.json``
    carrying the final step's spans and the resilience events that killed
    the run — the crash-safe shutdown path, not a happy-path export."""
    from trlx_tpu.resilience import NonFiniteUpdateError

    # step 0 completes cleanly (its stats land in the ring); step 1's loss
    # is poisoned and the halt policy raises out of learn()
    config = _obs_ppo_config(tmp_path).evolve(
        resilience=dict(update_guard="halt", fault_plan="nan_loss@step:1"),
    )
    with pytest.raises(NonFiniteUpdateError):
        _run_obs_ppo(config)

    doc = json.load(open(tmp_path / "logs" / "flightrec.json"))
    assert "NonFiniteUpdateError" in doc["reason"]
    records = doc["records"]
    span_names = {
        r["data"]["name"] for r in records if r["kind"] == "span"
    }
    # the final (poisoned) step's spans are in the ring
    assert "train_step" in span_names
    assert "generate" in span_names
    # resilience events: the guard counted the non-finite update through
    # the metrics tap before halting
    metric_names = {
        r["data"]["name"] for r in records if r["kind"] == "metric"
    }
    assert "resilience/nonfinite_updates" in metric_names
    # the per-step stats records rode along
    assert any(r["kind"] == "step" for r in records)


def test_engine_request_spans_and_flightrec_fault(tmp_path):
    """Continuous-batching run: per-request Engine lifecycle spans
    (queue wait → prefill → decode) land in the trace on per-slot tracks,
    ``engine/queue_wait_s`` rides the stats stream, and the deterministic
    ``flightrec_dump@step:N`` fault dumps mid-run without any crash."""
    config = _obs_ppo_config(tmp_path, continuous_batching=True).evolve(
        resilience=dict(fault_plan="flightrec_dump@step:1"),
    )
    _run_obs_ppo(config)

    trace = json.load(open(tmp_path / "logs" / "trace.json"))
    events = trace["traceEvents"]
    lifecycle = {
        name: [e for e in events if e["name"] == name]
        for name in ("engine/queue_wait", "engine/prefill", "engine/decode")
    }
    for name, evs in lifecycle.items():
        assert evs, f"no {name} events in the trace"
    # per-request ordering on a slot track: queue_wait → prefill → decode
    first_decode = lifecycle["engine/decode"][0]
    idx = first_decode["args"]["index"]
    chain = {
        name: next(e for e in evs if e["args"]["index"] == idx)
        for name, evs in lifecycle.items()
    }
    qw, pf, dec = (
        chain["engine/queue_wait"], chain["engine/prefill"], chain["engine/decode"]
    )
    assert qw["tid"] == pf["tid"] == dec["tid"]  # one slot track
    assert qw["ts"] + qw["dur"] <= pf["ts"] + 1e-3
    assert pf["ts"] + pf["dur"] <= dec["ts"] + 1e-3
    # slot tracks are labeled
    track_names = {
        e["args"]["name"] for e in events if e["name"] == "thread_name"
    }
    assert any(n.startswith("engine/slot") for n in track_names)

    records = [
        json.loads(l) for l in open(tmp_path / "logs" / "stats.jsonl")
    ]
    keys = set().union(*(set(r) for r in records))
    assert "engine/queue_wait_s" in keys

    # the fault-plan dump fired mid-run (no crash): reason names the fault
    doc = json.load(open(tmp_path / "logs" / "flightrec.json"))
    assert "flightrec_dump@step:1" in doc["reason"]
    assert any(r["kind"] == "span" for r in doc["records"])
