"""The learner runs one step ahead of its records (CPU, toy widths;
docs/OBSERVABILITY.md "One RL cycle", docs/RESILIENCE.md "Update guard").

``TPUBaseTrainer._learn_loop`` launches step N+1 before it lands step N wherever
N+1 is known and no boundary lies between the two. Here:

- the order of launches and landings over PPO cycles of 4 batches x 4 replays,
  and that nothing is in flight where the loop acts on the state;
- losses and final parameters bit-equal to a run whose every step is a
  boundary (``_boundary_after`` patched to say so: the loop as it was), for
  PPO, GRPO, SFT and ILQL;
- faults keep their meaning: preemption, ``nan_loss``, a rollback with a step
  in flight, a health trip's triage, emergency resume;
- the records still tile the learn phase, say which steps ran ahead and what
  that hid, and the benchmark's new metric file reads them.
"""

import hashlib
import json
import os
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import trlx_tpu.trlx as trlx
from trlx_tpu.data.default_configs import (
    default_grpo_config,
    default_ilql_config,
    default_ppo_config,
    default_sft_config,
)
from trlx_tpu.resilience import TrainingPreempted, set_active_plan
from trlx_tpu.trainer.base import TPUBaseTrainer
from trlx_tpu.utils.checkpoint import read_extra

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROMPTS = ["ab", "cd", "ef", "gh", "ij", "kl", "mn", "op"]
BATCHES, REPLAYS = 4, 4
CYCLE = BATCHES * REPLAYS  # optimizer steps a PPO cycle


@pytest.fixture(autouse=True)
def _quiet(monkeypatch):
    """No MFU thread (its AOT compile is noise here) and no inherited plan."""
    monkeypatch.setenv("TRLX_TPU_MFU", "0")
    monkeypatch.delenv("TRLX_TPU_FAULT_PLAN", raising=False)
    monkeypatch.delenv("TRLX_TPU_PROFILE", raising=False)
    set_active_plan(None)
    yield
    set_active_plan(None)


@pytest.fixture(scope="module")
def _no_mfu_thread():
    """``_quiet``'s switch for what a module-scoped fixture runs, which is
    built before the autouse fixture of its first test: set for the build and
    put back after it. Left set in ``os.environ`` it stayed behind for every
    later test of the worker, and ``tests/test_observability.py::
    test_ppo_smoke_emits_throughput_and_trace`` then found no
    ``throughput/mfu`` in its records (the driver's run of PR 59)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("TRLX_TPU_MFU", "0")
        yield


class Recorder:
    """Stand-in tracker: keeps every record the trainer logs."""

    def __init__(self):
        self.records = []

    def log(self, stats, step=None):
        self.records.append(dict(stats))

    def finish(self):
        pass


def reward_fn(samples, prompts, outputs, **kwargs):
    return [float(len(o)) + 0.1 * i for i, o in enumerate(outputs)]


def rl_config(tmp_path, method="ppo", replays=REPLAYS, rollouts=8 * BATCHES, **train):
    default = default_grpo_config if method == "grpo" else default_ppo_config
    extra = dict(group_size=4) if method == "grpo" else {}
    train = {**dict(
        seq_length=24, batch_size=8, total_steps=1000, eval_interval=1000,
        checkpoint_interval=1000, epochs=2, save_best=False, tracker=None,
        checkpoint_dir=str(tmp_path / "ckpts"), logging_dir=str(tmp_path / "logs"),
    ), **train}
    return default().evolve(
        train=train,
        model=dict(model_path="builtin:gpt2-test", num_layers_unfrozen=1),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        method=dict(
            num_rollouts=rollouts, chunk_size=8, ppo_epochs=replays,
            gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0, do_sample=True),
            **extra,
        ),
    )


def offline_config(tmp_path, method, **train):
    default = default_ilql_config if method == "ilql" else default_sft_config
    train = {**dict(
        seq_length=48, batch_size=8, total_steps=12, eval_interval=1000,
        checkpoint_interval=1000, epochs=3, tracker=None,
        checkpoint_dir=str(tmp_path / "ckpts"), logging_dir=str(tmp_path / "logs"),
    ), **train}
    extra = dict(steps_for_target_q_sync=3) if method == "ilql" else {}
    return default().evolve(
        train=train, model=dict(model_path="builtin:gpt2-test"),
        method=dict(gen_kwargs=dict(max_new_tokens=8), **extra),
    )


def _fingerprint(batch):
    items = batch._asdict() if hasattr(batch, "_asdict") else batch
    h = hashlib.sha1()
    for k in sorted(items):
        if hasattr(items[k], "shape"):
            h.update(k.encode() + np.ascontiguousarray(items[k]).tobytes())
    return h.hexdigest()[:12]


def spy(trainer, events, batches=None):
    """Log ``(what, step)`` as the loop goes: ``launch``, ``land`` (begun) and
    ``landed``, ``post_epoch``, ``save``, ``evaluate``, ``callback``."""
    trainer.tracker = Recorder()

    def wrap(name, note):
        fn = getattr(trainer, name)

        def wrapped(*a, **kw):
            done = note(*a, **kw)
            out = fn(*a, **kw)
            if done is not None:
                events.append(done)
            return out

        setattr(trainer, name, wrapped)

    def launch(batch, step=None):
        step = trainer.iter_count if step is None else step
        events.append(("launch", step))
        if batches is not None:
            batches.append((step, batch))

    def land(loop, flight, ahead_of=None):
        events.append(("land", flight.step))
        return ("landed", flight.step)

    wrap("train_step", launch)
    wrap("_land", land)
    for name in ("post_epoch_callback", "save", "evaluate", "post_backward_callback"):
        label = {"post_epoch_callback": "post_epoch", "post_backward_callback": "callback"}.get(name, name)
        wrap(name, lambda *a, _label=label, **kw: events.append((_label, trainer.iter_count)))


def in_flight(events, upto):
    """Steps launched before ``events[upto]`` whose landing had not begun by
    then (a landing opens with its fence)."""
    seen = events[:upto]
    return sum(w == "launch" for w, _ in seen) - sum(w == "land" for w, _ in seen)


def run_rl(config, hook=None):
    events, batches = [], []

    def init(trainer):
        spy(trainer, events, batches)
        if hook is not None:
            hook(trainer)

    trainer = trlx.train(reward_fn=reward_fn, prompts=PROMPTS, config=config, init_trainer_hook=init)
    return SimpleNamespace(trainer=trainer, events=events, batches=batches,
                           records=trainer.tracker.records,
                           steps=[r for r in trainer.tracker.records if "time/train_step" in r])


def run_offline(config, method, hook=None):
    events = []

    def init(trainer):
        spy(trainer, events)
        if hook is not None:
            hook(trainer)

    if method == "ilql":
        kwargs = dict(samples=[["prompt one", " good"], ["prompt two", " bad"]] * 16,
                      rewards=[1.0, 0.0] * 16)
    else:
        kwargs = dict(samples=[[f"question {i}?", f" answer {i}!"] for i in range(32)])
    trainer = trlx.train(config=config, init_trainer_hook=init, **kwargs)
    return SimpleNamespace(trainer=trainer, events=events, records=trainer.tracker.records,
                           steps=[r for r in trainer.tracker.records if "time/train_step" in r])


def leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(jax.device_get(tree))]


def losses(steps):
    return [{k: v for k, v in r.items() if k.startswith("losses/")} for r in steps]


# ---------------------------------------------------------------------------
# order: three PPO cycles of 4 batches x 4 replays (the third one step long)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def ppo_cycles(tmp_path_factory, _no_mfu_thread):
    tmp = tmp_path_factory.mktemp("ahead")
    learn = []  # (start, end) of each learn phase, on the loop's own clock

    def hook(trainer):
        collect, post_epoch = trainer.make_experience, trainer.post_epoch_callback

        def make_experience(*a, **kw):
            out = collect(*a, **kw)
            learn.append([trainer._host_gap_t0, None])
            return out

        def post_epoch_callback():
            learn[-1][1] = trainer._host_gap_t0  # the last step's fence
            return post_epoch()

        trainer.make_experience = make_experience
        trainer.post_epoch_callback = post_epoch_callback

    run = run_rl(rl_config(tmp, epochs=3, total_steps=2 * CYCLE + 1), hook)
    run.learn = learn
    return run


def test_launch_two_comes_before_land_one(ppo_cycles):
    events = ppo_cycles.events
    at = {e: i for i, e in enumerate(events)}
    assert len(ppo_cycles.steps) == 2 * CYCLE + 1
    # the second cycle: its first step after the collection, its last before
    # the next; every step between is launched before the one before it lands
    for step in range(CYCLE, 2 * CYCLE - 1):
        assert at[("launch", step + 1)] < at[("land", step)], step
        assert at[("land", step)] < at[("landed", step)] < at[("land", step + 1)]
    # depth one: never more than the landing step and one ahead of it
    assert max(in_flight(events, i) for i in range(len(events))) == 2
    # the job's first step lands before anything else is launched
    assert at[("landed", 0)] < at[("launch", 1)]
    assert [r["learn/ahead"] for r in ppo_cycles.steps] == (
        [0.0, 0.0] + [1.0] * (CYCLE - 2) + [0.0] + [1.0] * (CYCLE - 1) + [0.0])


def test_nothing_is_in_flight_at_post_epoch_callback(ppo_cycles):
    events = ppo_cycles.events
    epochs = [i for i, (what, _) in enumerate(events) if what == "post_epoch"]
    assert [events[i][1] for i in epochs] == [CYCLE, 2 * CYCLE]
    assert [in_flight(events, i) for i in epochs] == [0, 0]
    # the controller's callback: once a batch, after its last replay landed
    callbacks = [i for i, (what, _) in enumerate(events) if what == "callback"]
    assert [events[i][1] for i in callbacks] == list(range(REPLAYS, 2 * CYCLE + 1, REPLAYS))
    for i in callbacks:
        assert ("land", events[i][1] - 1) in events[:i]


def test_gap_and_step_tile_the_learn_phase(ppo_cycles):
    for cycle, (t0, t1) in enumerate(ppo_cycles.learn[:2]):
        steps = ppo_cycles.steps[cycle * CYCLE:(cycle + 1) * CYCLE]
        tiled = sum(r["time/step_gap"] + r["time/train_step"] for r in steps)
        assert tiled == pytest.approx(t1 - t0, abs=1e-3)
    for r in ppo_cycles.steps:
        if r["learn/ahead"]:
            assert r["time/step_gap"] == 0.0
        else:
            assert r["time/step_gap"] > 0.0
        assert 0.0 <= r["time/train_step_wait"] <= r["time/train_step"]
        assert r["time/train_step_dispatch"] > 0.0


def test_host_hidden_is_on_every_record_and_the_metric_file_reads_it(ppo_cycles):
    from chipbench import layers

    for r in ppo_cycles.steps:
        # a lower bound: what the landing before took after its fence, where
        # this step was still on the chip when that landing ended
        assert r["time/step_host_hidden"] >= 0.0
        if not r["learn/ahead"]:
            assert r["time/step_host_hidden"] == 0.0
        assert r["time/step_host_hidden"] < r["time/train_step"] + 1e-9
    with open(os.path.join(REPO, "chipbench", "layer_metrics", "learn_host_hidden_pct.json")) as f:
        spec = json.load(f)
    declared = {m["name"]: m for m in layers.job.load_benchmark()["per_layer"]}[spec["name"]]
    assert "workloads" not in declared  # every cell runs the loop
    assert {k: spec[k] for k in ("name", "unit", "better", "source", "layer", "moves")} == declared
    assert (spec["reducer"], spec["where"], spec["key"]) == ("stat_share", "steps", "time/step_host_hidden")
    cycle = {"collection": {}, "steps": ppo_cycles.steps[CYCLE:2 * CYCLE], "start": 0.0, "end": 2.0}
    hidden = sum(r["time/step_host_hidden"] for r in cycle["steps"])
    value = layers.reduce_one(spec, SimpleNamespace(cycles=[cycle]), None, None, 1)
    assert value == pytest.approx(100.0 * hidden / 2.0)
    # a program without the key (the parent of PR 53) reports nothing
    bare = [{"collection": {}, "steps": [{"time/train_step": 1.0}], "start": 0.0, "end": 2.0}]
    assert layers.reduce_one(spec, SimpleNamespace(cycles=bare), None, None, 1) is None


def test_spans_of_a_landing(ppo_cycles):
    events = [e for e in ppo_cycles.trainer.obs.tracer.events() if e.get("ph") == "X"]
    lands = sorted((e for e in events if e["name"] == "learn/land"), key=lambda e: e["ts"])
    launches = sorted((e for e in events if e["name"] == "train_step"), key=lambda e: e["ts"])
    assert len(lands) == len(launches) == 2 * CYCLE + 1
    assert [e["args"]["step"] for e in lands] == list(range(2 * CYCLE + 1))
    assert all("wait_s" in e["args"] for e in lands)  # the fence is the landing's
    assert all("wait_s" not in e.get("args", {}) for e in launches)
    # launch, landing and the host between them never overlap
    spans = sorted(lands + launches + [e for e in events if e["name"] == "learn/step_host"],
                   key=lambda e: e["ts"])
    for a, b in zip(spans, spans[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1.0, (a["name"], b["name"])  # microseconds


# ---------------------------------------------------------------------------
# the same device work: bit-equal to a loop that lands every step first
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["ppo", "grpo", "sft", "ilql"])
def test_bit_equal_to_a_run_of_boundaries(method, tmp_path, monkeypatch):
    def both(tag):
        if method in ("ppo", "grpo"):
            return run_rl(rl_config(tmp_path / tag, method, replays=2, rollouts=16, epochs=2))
        return run_offline(offline_config(tmp_path / tag, method), method)

    ahead = both("ahead")
    monkeypatch.setattr(TPUBaseTrainer, "_boundary_after", lambda self, flight, nxt: True)
    landed = both("landed")
    assert sum(r["learn/ahead"] for r in landed.steps) == 0.0
    assert sum(r["learn/ahead"] for r in ahead.steps) >= len(ahead.steps) // 3
    assert len(ahead.steps) == len(landed.steps) == ahead.trainer.iter_count
    assert losses(ahead.steps) == losses(landed.steps)  # to the last digit
    assert all(losses(ahead.steps))
    for a, b in zip(leaves(ahead.trainer.state), leaves(landed.trainer.state)):
        np.testing.assert_array_equal(a, b)
    # the calls that touch the state beside the step stand where they stood
    order = lambda run: [e for e in run.events if e[0] in ("landed", "callback", "post_epoch")]  # noqa: E731
    assert order(ahead) == order(landed)
    if method == "ilql":  # the target sync, every third update: nothing ahead of it
        at = {e: i for i, e in enumerate(ahead.events)}
        for step in range(2, 11, 3):
            assert at[("landed", step)] < at[("launch", step + 1)]
        assert at[("launch", 2)] < at[("land", 1)]


# ---------------------------------------------------------------------------
# boundaries act on a landed state
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def bounded(tmp_path_factory, _no_mfu_thread):
    tmp = tmp_path_factory.mktemp("bounded")
    return run_rl(rl_config(tmp, epochs=2, total_steps=22, checkpoint_interval=6, eval_interval=5))


@pytest.mark.parametrize("what, at_counts", [
    ("save", [6, 12, 18, 22]),          # checkpoint_interval, and the run's last
    ("evaluate", [0, 5, 10, 15, 20, 22]),  # the first, eval_interval, and the last
    ("landed", [21]),                   # total_steps
])
def test_checkpoint_eval_and_total_steps_see_a_landed_state(bounded, what, at_counts):
    events = bounded.events
    if what == "landed":
        # the run's last step lands with nothing behind it, and the loop ends
        i = events.index(("landed", 21))
        assert in_flight(events, i + 1) == 0 and ("launch", 22) not in events
        assert bounded.trainer.iter_count == 22 and len(bounded.steps) == 22
        return
    calls = [i for i, (w, _) in enumerate(events) if w == what]
    assert [events[i][1] for i in calls] == at_counts
    for i in calls:
        # every launch has landed, and iter_count says so
        assert in_flight(events, i) == 0
        assert sum(w == "launch" for w, _ in events[:i]) == events[i][1]
    # and between boundaries the loop still runs ahead
    assert sum(r["learn/ahead"] for r in bounded.steps) >= 8


# ---------------------------------------------------------------------------
# faults keep their meaning
# ---------------------------------------------------------------------------


def test_a_preemption_raised_during_a_landing_stops_before_the_next_launch(tmp_path):
    config = rl_config(tmp_path)

    def hook(trainer):
        land = trainer._land

        def landing(loop, flight, ahead_of=None):
            if flight.step == 5:
                assert ahead_of is not None and ahead_of.step == 6
                trainer.resilience.preemption.request("SIGTERM (test)")
            return land(loop, flight, ahead_of=ahead_of)

        trainer._land = landing

    events = []
    with pytest.raises(TrainingPreempted) as exc:
        trlx.train(reward_fn=reward_fn, prompts=PROMPTS, config=config,
                   init_trainer_hook=lambda t: (spy(t, events), hook(t)))
    # step 6 was in flight: it lands, the checkpoint is of it, step 7 never starts
    assert ("landed", 6) in events and ("launch", 7) not in events
    save = events.index(("save", 7))
    assert in_flight(events, save) == 0 and events.index(("landed", 6)) < save
    assert read_extra(exc.value.checkpoint_dir)["iter_count"] == 7


def test_nan_loss_poisons_the_step_it_names_and_no_other(tmp_path):
    config = rl_config(tmp_path, epochs=1).evolve(
        resilience=dict(update_guard="skip", fault_plan="nan_loss@step:6"))
    run = run_rl(config)
    assert [r["resilience/update_ok"] for r in run.steps] == [1.0] * 6 + [0.0] + [1.0] * 9
    at = {e: i for i, e in enumerate(run.events)}
    # the step the plan names is launched with nothing in flight; the one
    # after it runs ahead of its landing (the skip was applied on the device)
    assert at[("landed", 5)] < at[("launch", 6)] and at[("launch", 7)] < at[("land", 6)]
    for leaf in leaves(run.trainer.state.params):
        assert np.isfinite(leaf).all()


def test_a_rollback_discards_the_step_in_flight(tmp_path, monkeypatch):
    def go(tag):
        return run_rl(rl_config(tmp_path / tag, epochs=1, checkpoint_interval=4).evolve(
            resilience=dict(update_guard="rollback", fault_plan="nan_loss@step:5")))

    run = go("ahead")
    events = run.events
    # step 6 went to the chip before step 5's verdict; it is launched again
    assert sum(e == ("launch", 6) for e in events) == 2
    assert events.index(("launch", 6)) < events.index(("land", 5)) < events.index(("landed", 5))
    assert events.index(("landed", 5)) < len(events) - 1 - events[::-1].index(("launch", 6))
    discarded = [r for r in run.records if r.get("learn/discarded") == 1.0]
    assert len(discarded) == 1 and "time/train_step" not in discarded[0]
    assert any(k.startswith("losses/") for k in discarded[0])
    # it counts as no update, and the batch runs the replays it would have run
    assert run.trainer.iter_count == CYCLE and len(run.steps) == CYCLE
    assert sum(e == ("landed", 6) for e in events) == 1
    assert any(r.get("resilience/rollbacks", 0) >= 1 for r in run.records)
    ring = [r["data"] for r in run.trainer.obs.flightrec.snapshot() if r["kind"] == "resilience"]
    assert {"event": "discarded_in_flight", "step": 6} in ring
    # the same updates as a loop that lands every step before the next launch
    monkeypatch.setattr(TPUBaseTrainer, "_boundary_after", lambda self, flight, nxt: True)
    landed = go("landed")
    assert not any("learn/discarded" in r for r in landed.records)
    assert losses(run.steps) == losses(landed.steps)
    for a, b in zip(leaves(run.trainer.state), leaves(landed.trainer.state)):
        np.testing.assert_array_equal(a, b)


def test_a_health_trip_triages_the_batch_of_the_step_that_tripped(tmp_path):
    # one replay a batch: the step launched ahead has placed the NEXT batch
    # by the time the tripped step lands
    config = rl_config(tmp_path, replays=1, epochs=1).evolve(
        resilience=dict(fault_plan="health_trip@step:1"))
    quiet = []

    def hook(trainer):
        dump = trainer._dump_triage

        def dumping(reason, stats):
            state = jax.tree_util.tree_leaves(trainer.state.params)[0]
            quiet.append(state.is_ready())  # the step in flight was waited for
            return dump(reason, stats)

        trainer._dump_triage = dumping

    run = run_rl(config, hook)
    at = {e: i for i, e in enumerate(run.events)}
    assert at[("launch", 2)] < at[("land", 1)]
    batches = dict(run.batches)
    assert _fingerprint(batches[1]) != _fingerprint(batches[2])
    with np.load(tmp_path / "logs" / "triage" / "step1.npz") as f:
        arrays = {k: f[k] for k in f.files}
    meta = json.loads(bytes(arrays.pop("__meta__")).decode())
    assert meta["reason"].startswith("health:") and meta["step"] == 1
    tripped = batches[1]._asdict()
    for key in ("query_tensors", "response_tensors", "rewards"):
        np.testing.assert_array_equal(arrays[key], np.asarray(tripped[key]))
    assert quiet and all(quiet)


def test_emergency_resume_issues_the_same_device_calls(tmp_path):
    def calls(run):
        return [(step, _fingerprint(batch)) for step, batch in run.batches]

    def config(tag, **kw):
        return rl_config(tmp_path / tag, replays=2, rollouts=16, epochs=2, **kw)

    whole = run_rl(config("a"))
    assert whole.trainer.iter_count == 8
    with pytest.raises(TrainingPreempted):
        run_rl(config("b").evolve(resilience=dict(fault_plan="sigterm@step:5")))
    resumed = run_rl(config("b", resume_from_checkpoint=True))
    assert resumed.trainer.iter_count == 8
    # the fast-forward launches nothing; what follows is the whole run's tail
    assert calls(resumed) == calls(whole)[5:]
    assert losses(resumed.steps) == losses(whole.steps)[5:]
    for a, b in zip(leaves(whole.trainer.state), leaves(resumed.trainer.state)):
        np.testing.assert_array_equal(a, b)
    assert whole.trainer.kl_ctl.value == resumed.trainer.kl_ctl.value


# ---------------------------------------------------------------------------
# the benchmark's tracker hook
# ---------------------------------------------------------------------------


def test_the_harness_sees_a_cycles_last_record_after_its_fence(tmp_path):
    """``chipbench/run.py::Harness`` stands in for the tracker and stamps a
    cycle's end where its last step record is logged: that step, and every
    step before it, has to be off the chip by then."""
    from chipbench.run import Harness

    seen = []

    class Toy(Harness):
        def _on_step(self, stats, now):
            self.open["steps"].append(stats)
            last = len(self.open["steps"]) == self.shape["steps"]
            state = jax.tree_util.tree_leaves((self.trainer.state, self.trainer._landing_batch))
            seen.append((last, in_flight(events, len(events)),
                         all(getattr(x, "is_ready", lambda: True)() for x in state)))
            if last:
                self.open = {"steps": []}

        def _on_collection(self, stats):
            self.open = {"steps": []}

    harness = Toy(SimpleNamespace(trace=0, seed=0), None, None, None)
    events = []

    def hook(trainer):
        spy(trainer, events)
        harness.trainer, harness.shape = trainer, {"steps": CYCLE}
        trainer.tracker = harness

    trlx.train(reward_fn=reward_fn, prompts=PROMPTS, config=rl_config(tmp_path), init_trainer_hook=hook)
    assert len(seen) == 2 * CYCLE
    # a cycle's last record: nothing launched is still unfenced, the state is
    # there; mid-cycle the step launched ahead is on the chip meanwhile
    assert [s for s in seen if s[0]] == [(True, 0, True)] * 2
    assert {n for last, n, _ in seen if not last} == {0, 1}
    assert sum(n for last, n, _ in seen if not last) == 2 * CYCLE - 3
