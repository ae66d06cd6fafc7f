"""The memory account (docs/OBSERVABILITY.md "The memory account"; PR 51): what
the chip must hold while the job's programs run, told apart as state, code and
temporaries on every record.

CPU, toy configs. The CPU device reports no ``memory_stats()``, so every test
that needs the allocator's bytes sets the reader (``gauge.read``,
``trainer.obs.memory.read``), and XLA:CPU counts no generated code, so the
tests of the code column plant a compiler's row (``ProgramBytes.of``). The
need is the allocator's peak + the region the runtime reserves for running
programs: where the set reader reports no reservation there is no need.
"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import trlx_tpu.trlx as trlx
from chipbench import layers
from trlx_tpu.data.default_configs import default_grpo_config, default_ppo_config
from trlx_tpu.observability import tracing
from trlx_tpu.observability.watchdogs import DeviceMemory, DeviceMemoryGauge, shard_bytes
from trlx_tpu.utils import programs
from trlx_tpu.utils.programs import ProgramBytes, ProgramStore, stored_program

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRICS = {
    "hbm_state_gib": "memory/state_bytes", "hbm_code_gib": "memory/code_bytes",
    "hbm_temp_gib": "memory/device_reserved_bytes", "hbm_need_gib": "memory/need_bytes",
}
IN_USE = 1000.0 * 2**20  # what the set reader says is in use, at every read
RESERVED = 300.0 * 2**20  # and what the runtime keeps for running programs
STORE_GAUGES = {  # what needs the job's program store
    "memory/params_bytes", "memory/opt_state_bytes", "memory/ref_bytes", "memory/state_bytes",
    "memory/code_bytes", "memory/programs_resident", "memory/temp_bytes_max", "memory/untracked_bytes",
}
STEP_GAUGES = STORE_GAUGES | {"memory/need_bytes", "memory/device_reserved_bytes"}
PROGRAMS = ("rollout_generate", "score_fn", "train_step")


def _gauge(read):
    gauge = DeviceMemoryGauge()
    gauge.read = read
    return gauge


def _spec(name):
    with open(os.path.join(REPO, "chipbench", "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# a row of bytes for every held executable
# ---------------------------------------------------------------------------


@pytest.fixture()
def true_compiles():
    """An entry is written only for an executable compiled in earnest
    (``tests/test_program_store.py``): the loaded twin needs one."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def step(w, x):
    h = jnp.tanh(x @ w)
    return w - 0.1 * (x.T @ h), jnp.sum(h)


def test_a_compiled_and_a_loaded_executable_carry_the_same_row(true_compiles):
    args = jnp.ones((32, 32)), jnp.ones((8, 32))
    first = stored_program("account_step", step, ["one key"], donate_argnums=(0,))
    first(*args)
    second = stored_program("account_step", step, ["one key"], donate_argnums=(0,))
    second(jnp.ones((32, 32)), args[1])
    (compiled,), (loaded,) = first._held.values(), second._held.values()
    assert not compiled.loaded and loaded.loaded
    assert compiled.bytes == loaded.bytes and compiled.bytes is not None
    row = compiled.bytes
    assert row.arguments == 32 * 32 * 4 + 8 * 32 * 4 and row.aliased == 32 * 32 * 4
    assert row.outputs >= 32 * 32 * 4 + 4
    assert row.live == row.arguments + row.outputs - row.aliased


def _planted(monkeypatch):
    """XLA:CPU counts no generated code: give every executable 1000 bytes of
    it and its real arguments as temporaries, so sums and maxima can be told."""
    real = ProgramBytes.of.__func__

    def of(cls, compiled):
        row = real(cls, compiled)
        return row._replace(code=1000, temp=row.arguments)

    monkeypatch.setattr(ProgramBytes, "of", classmethod(of))


def test_code_is_summed_over_signatures_and_temporaries_are_their_largest(monkeypatch):
    _planted(monkeypatch)
    store = ProgramStore()
    f = store.program("account_sum", lambda x: x * 2.0)
    for n in (4, 64, 16):
        f(jnp.ones((n,)))
    g = store.program("account_sum", lambda x: x + 1.0, "another site")  # one name, two programs
    g(jnp.ones((8,)))
    h = store.program("account_small", lambda x: x - 2.0)
    h(jnp.ones((2,)))
    account = store.account()
    # resident, code, the largest temporaries, the largest arguments + outputs - aliased
    assert account.pop("by_program") == {"account_sum": [4, 4000, 64 * 4, 2 * 64 * 4],
                                         "account_small": [1, 1000, 2 * 4, 2 * 2 * 4]}
    (largest,) = [held.bytes for held in f._held.values() if held.bytes.temp == 64 * 4]
    assert account == {"code": 5000, "resident": 5, "temp": 64 * 4, "temp_program": "account_sum",
                       "temp_program_peak": largest.peak}


def test_the_table_has_the_rows_of_the_store_it_is_given_and_no_other(monkeypatch):
    """The sink's seconds are the process's; the bytes are one job's: a second
    trainer's store, or one that was dropped, adds nothing to this one's."""
    _planted(monkeypatch)
    mine, other = ProgramStore(), ProgramStore()
    f = mine.program("account_table", lambda x: x * 2.0)
    f(jnp.ones((2**20,)))
    g = other.program("account_table", lambda x: x / 2.0, "another job's")
    g(jnp.ones((8,)))
    k = other.program("account_other", lambda x: x + 3.0)
    k(jnp.ones((8,)))
    table = tracing.programs_table({}, rows=10**6, held=mine.account()["by_program"])
    assert table.splitlines()[0].endswith("resident code MiB temp GiB live GiB")
    line = next(ln for ln in table.splitlines() if ln.startswith("account_table"))
    assert line.split()[-4:] == ["1", "0.0", "0.004", "0.008"]  # 1000 bytes; 4 MiB; 8 MiB
    short, long = 32 + 6 * 9, 32 + 10 * 9  # a name and the runtime's six columns; with the four of bytes
    for ln in table.splitlines()[1:]:  # another job's program has no bytes here
        assert len(ln) == (long if ln.startswith("account_table") else short), ln
    bare = tracing.programs_table({}, rows=10**6)
    assert all(len(ln) == short for ln in bare.splitlines()[1:])


def test_a_once_programs_code_leaves_the_account_after_its_call(monkeypatch):
    _planted(monkeypatch)
    store = ProgramStore()
    kept = store.program("account_kept", lambda x: x * 3.0)
    once = store.program("account_once", lambda x: x - 1.0, once=True)
    kept(jnp.ones((4,)))
    gauge = _gauge(lambda: DeviceMemory(IN_USE, IN_USE, 16.0 * 2**30))
    assert gauge.collect(store.account())["memory/code_bytes"] == 1000.0
    np.testing.assert_array_equal(once(jnp.ones((4,))), np.zeros((4,)))
    out = gauge.collect(store.account())
    assert out["memory/code_bytes"] == 1000.0 and out["memory/programs_resident"] == 1.0
    assert store.account()["by_program"]["account_once"] == [0, 0, 0, 0]  # named, and holds nothing
    (held,) = once._held.values()
    assert held.call is None and held.bytes.code == 1000  # the row outlives the executable


def test_the_plain_path_has_no_row_and_no_account(monkeypatch):
    monkeypatch.setattr(programs, "store_dir", lambda: None)
    store = ProgramStore()
    f = store.program("account_plain", lambda x: x * 2.0)
    f(jnp.ones((4,)))
    assert not f._held and store.account() is None
    gauge = _gauge(lambda: DeviceMemory(IN_USE, IN_USE, 16.0 * 2**30))
    gauge.note_state({"w": jnp.ones((4,))}, None, None)
    assert not any(k in STEP_GAUGES for k in gauge.collect(store.account()))


def test_shard_bytes_counts_a_leafs_largest_shard():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("a", "b"))
    spec = jax.sharding.PartitionSpec
    put = lambda x, *axes: jax.device_put(x, jax.sharding.NamedSharding(mesh, spec(*axes)))  # noqa: E731
    tree = {"split": put(jnp.ones((8, 4), jnp.float32), "a", "b"),
            "whole": put(jnp.ones((6,), jnp.bfloat16)), "host": np.ones((3,), np.int32),
            "abstract": jax.ShapeDtypeStruct((2, 2), jnp.float32), "none": None, "n": 3}
    assert shard_bytes(tree) == 8 * 4 * 4 // 4 + 6 * 2 + 3 * 4 + 2 * 2 * 4


# ---------------------------------------------------------------------------
# the gauge's arithmetic
# ---------------------------------------------------------------------------


ACCOUNT = {"code": 64, "resident": 3, "temp": 900, "temp_program": "s", "temp_program_peak": 0}


def test_untracked_is_what_is_in_use_less_state_less_code():
    gauge = _gauge(lambda: DeviceMemory(1000.0, 5000.0, 16000.0))
    gauge.note_state({"w": np.ones((10,), np.float32)}, {"m": np.ones((5,), np.float32)},
                     {"r": np.ones((5,), np.float32)})
    out = gauge.collect(ACCOUNT)
    assert out["memory/state_bytes"] == 40.0 + 20.0 + 20.0
    assert out["memory/untracked_bytes"] == 1000.0 - 80.0 - 64.0
    assert (out["memory/code_bytes"], out["memory/programs_resident"], out["memory/temp_bytes_max"]) == (64, 3, 900)


@pytest.mark.parametrize("reserved, programs, need", [
    (800.0, ACCOUNT, 450.0 + 800.0),  # a TPU: the peak + the runtime's one region
    (800.0, None, 450.0 + 800.0),  # the allocator's alone: it needs no program store
    (None, ACCOUNT, None),  # an allocator that reports no reservation (the CPU): no need
    (0.0, ACCOUNT, None),  # or none yet: no program has been loaded
])
def test_need_is_the_allocators_peak_and_the_runtimes_reservation(reserved, programs, need):
    """A TPU's runtime keeps ONE region for running programs' temporaries,
    outside the bytes in use and their peak, sized by the largest program it
    has loaded: that is what is held on top of the reading, not any compiler's
    figure (``memory/temp_bytes_max`` stays the planning estimate beside it)."""
    gauge = _gauge(lambda: DeviceMemory(400.0, 450.0, 16000.0, reserved=reserved))
    gauge.note_state({"w": np.ones((10,), np.float32)}, None, None)
    out = gauge.collect(programs)
    assert out.get("memory/need_bytes") == need
    assert out.get("memory/device_reserved_bytes") == reserved
    assert ("memory/temp_bytes_max" in out) == (programs is not None)
    assert not (STORE_GAUGES & set(out)) or programs is not None


def test_the_account_is_logged_once_and_again_only_when_the_need_has_grown(trlx_log_records):
    now = [DeviceMemory(2.0 * 2**30, 3.0 * 2**30, 16.0 * 2**30, reserved=2**30)]
    gauge = _gauge(lambda: now[0])
    gauge.note_state({"w": np.ones((10,), np.float32)}, None, None)
    account = {"code": 2**20, "resident": 3, "temp": 2**31, "temp_program": "score_fn",
               "temp_program_peak": 3 * 2**30}
    gauge.collect(account)
    gauge.log_account()
    gauge.log_account()  # nothing grew: no second line
    now[0] = now[0]._replace(peak=3.02 * 2**30)
    gauge.collect(account)
    gauge.log_account()  # by under a hundredth: none either
    lines = [r.getMessage() for r in trlx_log_records if "memory account" in r.getMessage()]
    assert len(lines) == 1
    assert "memory/need_bytes 4.000 GiB = peak 3.000 + reserved of 16.000" in lines[0]
    assert "largest temporaries by the compiler 2.000 GiB (score_fn; its own peak by the compiler 3.000)" in lines[0]
    assert "by the runtime 1.000" in lines[0] and "code 0.001 GiB in 3 programs" in lines[0]
    now[0] = now[0]._replace(reserved=2.0 * 2**30)  # a larger program was loaded
    gauge.collect(account)
    gauge.log_account()
    lines = [r.getMessage() for r in trlx_log_records if "memory account" in r.getMessage()]
    assert len(lines) == 2 and "memory/need_bytes 5.020 GiB" in lines[1]


def test_collect_may_run_while_another_thread_builds_programs():
    """Actor threads (``async_rl.mode: thread``) reach the store while the
    learner's step record walks it: the walk is over snapshots, and the gauge
    keeps no table of its own that two threads write."""
    import threading

    store = ProgramStore()
    gauge = _gauge(lambda: DeviceMemory(IN_USE, IN_USE, 16.0 * 2**30, reserved=RESERVED))
    stop, errors = threading.Event(), []

    def build():
        n = 1
        while not stop.is_set() and n < 200:
            try:
                store.program(f"account_thread_{n}", lambda x: x + 1.0)(jnp.ones((n,)))
            except Exception as e:  # pragma: no cover - the failure this test is for
                errors.append(e)
            n += 1

    thread = threading.Thread(target=build, name="trlx-test-account", daemon=True)
    thread.start()
    try:
        for _ in range(300):
            out = gauge.collect(store.account())
            assert out["memory/need_bytes"] == IN_USE + RESERVED
    finally:
        stop.set()
        thread.join()
    assert not errors and gauge.collect(store.account())["memory/programs_resident"] >= 1.0


# ---------------------------------------------------------------------------
# toy cycles
# ---------------------------------------------------------------------------


def _config(method, tmp, **model):
    default = default_grpo_config if method == "grpo" else default_ppo_config
    extra = dict(group_size=4) if method == "grpo" else {}
    return default().evolve(
        train=dict(seq_length=24, batch_size=8, total_steps=8, eval_interval=100,
                   checkpoint_interval=100, epochs=2, save_best=False, tracker=None,
                   checkpoint_dir=str(tmp / "ckpts"), logging_dir=str(tmp / "logs"),
                   rollout_pipeline_depth=0),
        model=dict(model_path="builtin:gpt2-test", num_layers_unfrozen=1, **model),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        method=dict(num_rollouts=16, chunk_size=8, ppo_epochs=2,
                    gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0, do_sample=True), **extra),
    )


def _run(method, tmp, reserved=RESERVED):
    records = []

    def hook(trainer):
        trainer.tracker = types.SimpleNamespace(
            log=lambda stats, step=None: records.append(dict(stats)), finish=lambda: None)
        trainer.obs.memory.read = lambda: DeviceMemory(IN_USE, 2 * IN_USE, 16.0 * 2**30, reserved)

    trainer = trlx.train(
        reward_fn=lambda samples, prompts, outputs, **kw: [float(len(o)) + 0.1 * i for i, o in enumerate(outputs)],
        prompts=["ab", "cd", "ef", "gh", "ij", "kl", "mn", "op"], config=_config(method, tmp),
        init_trainer_hook=hook)
    collections = [r for r in records if "time/exp" in r]
    steps = [r for r in records if "time/train_step" in r]
    harness = types.SimpleNamespace(cycles=[{"collection": collections[-1], "steps": steps}])
    return types.SimpleNamespace(trainer=trainer, collections=collections, steps=steps, harness=harness)


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """``method -> run``: two cycles of four steps each, a stand-in tracker
    and a set reader; ``plain``: PPO with no store directory, and an allocator
    that reports no reservation."""
    made = {}

    def get(method):
        if method not in made:
            keep = programs.store_dir
            if method == "plain":
                programs.store_dir = lambda: None
            try:
                made[method] = _run("ppo" if method == "plain" else method, tmp_path_factory.mktemp(method),
                                    **(dict(reserved=None) if method == "plain" else {}))
            finally:
                programs.store_dir = keep
        return made[method]

    yield get
    made.clear()


@pytest.mark.parametrize("method", ["ppo", "grpo"])
def test_the_gauges_ride_the_step_record(runs, method):
    run = runs(method)
    assert len(run.collections) == 2 and len(run.steps) == 8
    for record in run.steps:
        assert STEP_GAUGES <= set(record), STEP_GAUGES - set(record)
        assert record["memory/need_bytes"] == record["memory/device_peak_bytes"] + RESERVED == 2 * IN_USE + RESERVED
        assert record["memory/untracked_bytes"] == IN_USE - record["memory/state_bytes"] - record["memory/code_bytes"]
        assert record["memory/programs_resident"] >= 3.0
    for record in run.collections:  # the account is the step record's
        assert not [k for k in record if k in STEP_GAUGES]


@pytest.mark.parametrize("program", PROGRAMS)
def test_a_large_program_has_its_row_in_the_account_and_in_the_table(runs, program):
    trainer = runs("ppo").trainer
    account = trainer.programs.account()
    resident, code, temp, live = account["by_program"][program]
    held = [h.bytes for p in trainer.programs._programs if p.name == program for h in p._held.values()]
    assert resident == len(held) >= 1 and temp == max(r.temp for r in held) > 0
    assert live == max(r.arguments + r.outputs - r.aliased for r in held) > 0
    assert account["temp"] >= temp and account["resident"] >= 3
    if program == "train_step":
        assert all(r.aliased > 0 for r in held)  # the donated state
    table = tracing.programs_table({}, rows=10**6, held=account["by_program"])
    line = next(ln for ln in table.splitlines() if ln.split()[0] == program)
    assert line.split()[-4] == str(resident) and float(line.split()[-2]) == pytest.approx(temp / 2**30, abs=5e-4)


def test_state_bytes_are_the_trees_leaf_bytes_and_fall_under_lora(runs, tmp_path):
    from trlx_tpu.trainer.ppo import PPOTrainer

    trainer = runs("ppo").trainer
    record = runs("ppo").steps[-1]
    leaf_bytes = lambda tree: float(sum(x.nbytes for x in jax.tree_util.tree_leaves(tree)))  # noqa: E731
    assert record["memory/params_bytes"] == leaf_bytes(trainer.state.params)
    assert record["memory/opt_state_bytes"] == leaf_bytes(trainer.state.opt_state)
    assert record["memory/ref_bytes"] == leaf_bytes(trainer.ref_params) > 0
    assert record["memory/state_bytes"] == sum(
        record[f"memory/{k}_bytes"] for k in ("params", "opt_state", "ref"))
    lora = PPOTrainer(_config("ppo", tmp_path, peft_kwargs=dict(
        peft_type="lora", r=4, lora_alpha=8, modified_modules=["q_proj", "v_proj"])),
        reward_fn=lambda samples, **kw: [0.0] * len(samples))
    lora._note_state_bytes()
    out = lora.obs.memory.collect(lora.programs.account())
    assert out["memory/params_bytes"] > record["memory/params_bytes"]  # the adapters
    assert out["memory/opt_state_bytes"] < record["memory/opt_state_bytes"] / 2  # no moments for a frozen kernel
    assert out["memory/state_bytes"] < record["memory/state_bytes"]


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_metric_file_reads_a_key_the_step_record_carries(runs, name):
    spec = _spec(name)
    assert (spec["reducer"], spec["key"], spec["moves"], spec["source"], spec["layer"]) == (
        "stat_median", METRICS[name], "peak_hbm_gib", "program_counter", "entry / runtime")
    assert spec["scale"] == 2.0**-30 and spec["better"] == "lower" and spec["unit"] == "GiB"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert "workloads" not in entry and entry["moves"] == "peak_hbm_gib"
    if name in ("hbm_temp_gib", "hbm_need_gib"):  # what bounds the headroom, and moves no peak
        assert "NOT part of peak_hbm_gib" in spec["reads"]
    run = runs("grpo")
    values = [r[spec["key"]] for r in run.steps]
    assert layers.reduce_one(spec, run.harness, None, None, 1) == 2.0**-30 * float(np.median(values))


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_trainer_on_the_plain_path_has_no_account_and_the_metric_is_left_out(runs, name):
    run = runs("plain")
    assert len(run.steps) == 8
    for record in run.steps + run.collections:
        assert not [k for k in record if k in STEP_GAUGES]
        assert "memory/host_rss_bytes" in record or "time/exp" in record  # the allocator's own stay
    assert run.trainer.programs.account() is None
    assert layers.reduce_one(_spec(name), run.harness, None, None, 1) is None


# ---------------------------------------------------------------------------
# the repaired span: the fence first, the sampler's counters after it
# ---------------------------------------------------------------------------


def test_a_self_drafting_generate_fences_before_it_fetches_its_counters(tmp_path, monkeypatch):
    from trlx_tpu.trainer import base
    from trlx_tpu.trainer.ppo import PPOTrainer

    config = default_ppo_config().evolve(
        train=dict(seq_length=36, batch_size=4, total_steps=2, epochs=1, tracker=None,
                   checkpoint_dir=str(tmp_path / "ckpts"), logging_dir=str(tmp_path / "logs")),
        model=dict(model_path="builtin:k-exaone-test", num_layers_unfrozen=1,
                   model_extra_kwargs=dict(moe_experts_held=2, moe_first_expert=2)),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        parallel=dict(param_dtype="float32", compute_dtype="float32"),
        method=dict(num_rollouts=8, chunk_size=8, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=8, min_new_tokens=8, top_k=0, top_p=1.0, do_sample=True)),
    )
    trainer = PPOTrainer(config, reward_fn=lambda samples, **kw: [0.0] * len(samples))
    assert trainer.self_drafts
    closed_at_fetch = []
    device_get = jax.device_get

    def fetching(tree):
        if isinstance(tree, dict) and "acceptance_rate" in tree:
            spans = [e for e in trainer.obs.tracer.events() if e["name"] == "generate"]
            closed_at_fetch.append((len(spans), bool(spans) and "wait_s" in spans[-1]["args"]))
        return device_get(tree)

    monkeypatch.setattr(base.jax, "device_get", fetching)
    ids = np.random.RandomState(0).randint(97, 123, size=(4, 12)).astype(np.int32)
    out = trainer.generate(ids, np.ones_like(ids))
    assert closed_at_fetch == [(1, True)]  # the span had closed, fence and all, before the six scalars landed
    sp = trainer.last_generate_span
    assert sp.t1 is not None and sp.dispatch + sp.wait == pytest.approx(sp.duration)
    assert trainer.last_spec_stats["rollout/spec_rounds"] >= 4
    assert out.response_tokens.shape == (4, 8)
