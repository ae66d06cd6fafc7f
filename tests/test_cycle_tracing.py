"""The span tree and the stats of one RL cycle, the same in PPO and GRPO
(CPU, toy widths; docs/OBSERVABILITY.md "One RL cycle").

One toy run per trainer flavor (two chunks a collection, two optimizer
steps), recorded through a stand-in tracker, then:

- collection records carry one vocabulary in both trainers, each ``time/*``
  key a sum over the collection's chunks;
- step records carry ``time/step_gap``, ``learn/pad_frac`` and ``learn/step_width``;
- the spans cover the cycle, carry ``cycle=<n>``, and tile the learn phase;
- the first collection's store holds the same arrays at either pipeline
  depth: the pipelined collector builds what the serial one builds;
- the three programs have the module names the benchmark's trace metrics
  match on, and ``score_fn`` names exactly one of them;
- every per-layer metric file this vocabulary feeds reads a key or a name
  the program really emits.
"""

import dataclasses
import inspect
import json
import os
import re

import jax
import numpy as np
import pytest

import trlx_tpu.trlx as trlx
from trlx_tpu.data.default_configs import default_grpo_config, default_ppo_config
from trlx_tpu.ops import flash_attention as fa
from trlx_tpu.parallel import shard_batch

COLLECTION_KEYS = {
    "time/generate", "time/score", "time/reward", "time/collect_host",
    "rollout/decode_steps", "time/decode_step",
}
NEW_SPANS = {
    "collect/experience", "collect/prompts", "collect/finalize",
    "learn/loader", "learn/step_host", "learn/land",
}
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW_LAYER_METRICS = (
    "decode_step_ms", "score_share_pct", "collect_host_share_pct", "step_gap_ms",
    "learn_pad_pct", "generate_device_share_pct", "train_device_share_pct",
    "flash_fwd_device_ms", "flash_bwd_device_ms", "learn_grad_param_pct",
)


class Recorder:
    """Stand-in tracker: keeps every record the trainer logs."""

    def __init__(self):
        self.records = []

    def log(self, stats, step=None):
        self.records.append(dict(stats))

    def finish(self):
        pass


def _toy_run(flavor, tmp_path):
    method, depth = flavor
    default = default_grpo_config if method == "grpo" else default_ppo_config
    extra = dict(group_size=4) if method == "grpo" else {}
    config = default().evolve(
        train=dict(
            seq_length=24, batch_size=8, total_steps=2, eval_interval=10,
            checkpoint_interval=10, epochs=1, save_best=False, tracker=None,
            checkpoint_dir=str(tmp_path / "ckpts"), logging_dir=str(tmp_path / "logs"),
            rollout_pipeline_depth=depth,
        ),
        model=dict(model_path="builtin:gpt2-test", num_layers_unfrozen=1),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        method=dict(
            num_rollouts=16, chunk_size=8, ppo_epochs=1,
            gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0, do_sample=True),
            **extra,
        ),
    )
    recorder = Recorder()
    generated = []  # (prompt width, response mask) of every rollout chunk

    def hook(trainer):
        trainer.tracker = recorder
        generate = trainer.generate

        def capturing(input_ids, *a, **kw):
            out = generate(input_ids, *a, **kw)
            if not kw.get("eval_mode", False):
                generated.append((np.asarray(input_ids).shape[1],
                                  np.asarray(out.response_mask)))
            return out

        trainer.generate = capturing

    def reward_fn(samples, prompts, outputs, **kwargs):
        return [float(len(o)) + 0.1 * i for i, o in enumerate(outputs)]

    prompts = ["ab", "cd", "ef", "gh", "ij", "kl", "mn", "op"]
    trainer = trlx.train(reward_fn=reward_fn, prompts=prompts, config=config,
                         init_trainer_hook=hook)
    return {"trainer": trainer, "records": recorder.records, "generated": generated,
            "events": trainer.obs.tracer.events(), "method": method, "depth": depth}


@pytest.fixture(scope="module")
def toy_runs(tmp_path_factory):
    """``flavor -> run``, each flavor run once for the module: a test may ask
    for another flavor's run beside its own."""
    runs = {}

    def get(flavor):
        if flavor not in runs:
            runs[flavor] = _toy_run(flavor, tmp_path_factory.mktemp("cycle"))
        return runs[flavor]

    yield get
    runs.clear()


@pytest.fixture(scope="module", params=[("ppo", 2), ("ppo", 0), ("grpo", 0), ("grpo", 2)],
                ids=["ppo-pipelined", "ppo-serial", "grpo", "grpo-pipelined"])
def run(request, toy_runs):
    return toy_runs(request.param)


def _spans(run, name, cycle=None):
    return [e for e in run["events"] if e["name"] == name and e.get("ph") == "X"
            and (cycle is None or e.get("args", {}).get("cycle") == cycle)]


def test_collection_keys_are_sums_over_chunks(run):
    collections = [r for r in run["records"] if "time/exp" in r]
    assert len(collections) == 1  # the run ends inside its first cycle
    rec = collections[0]
    assert COLLECTION_KEYS <= set(rec), sorted(COLLECTION_KEYS - set(rec))
    assert rec["time/exp_score"] == rec["time/reward"]
    # two chunks a collection: the fenced generate spans add up
    spans = [e for e in _spans(run, "generate", cycle=1)
             if not e["args"].get("eval_mode")]
    assert len(spans) == len(run["generated"]) == 2
    assert rec["time/generate"] == pytest.approx(sum(e["dur"] for e in spans) * 1e-6, rel=1e-9)
    assert rec["time/score"] == pytest.approx(
        sum(e["dur"] for e in _spans(run, "score", cycle=1)) * 1e-6, rel=1e-9)
    assert rec["time/reward"] == pytest.approx(
        sum(e["dur"] for e in _spans(run, "reward", cycle=1)) * 1e-6, rel=1e-9)
    steps = sum(int(mask.sum(axis=1).max()) for _, mask in run["generated"])
    assert rec["rollout/decode_steps"] == steps > 0
    assert rec["time/decode_step"] == pytest.approx(rec["time/generate"] / steps)
    assert 0.0 < rec["time/collect_host"] < rec["time/exp"] - rec["time/generate"]


def test_store_is_the_one_the_separate_collectors_built(run, toy_runs):
    """The first collection's store against that of the same method's run at
    the other pipeline depth, in this process: field by field the same arrays
    (dtype, shape, bytes). Until PR 29 PPO and GRPO each had a collector of
    their own, and the pipelined path a third; a digest of the bytes pinned
    the machine's float arithmetic with them."""
    # the run ended before the post-epoch refill: the store is the first
    # collection's
    history = run["trainer"].store.history
    other = toy_runs((run["method"], 0 if run["depth"] else 2))["trainer"].store.history
    assert len(history) == len(other) == 16
    for i, (element, twin) in enumerate(zip(history, other)):
        for field in dataclasses.fields(element):
            got, want = getattr(element, field.name), getattr(twin, field.name)
            if want is None:
                assert got is None, (i, field.name)
                continue
            got, want = np.asarray(got), np.asarray(want)
            assert (got.dtype, got.shape) == (want.dtype, want.shape), (i, field.name)
            assert got.tobytes() == want.tobytes(), (i, field.name)


def test_step_records_carry_gap_and_padding(run):
    trainer = run["trainer"]
    steps = [r for r in run["records"] if "time/train_step" in r]
    assert len(steps) == 2
    assert all(r["time/step_gap"] > 0 for r in steps)
    # the run ended before the post-epoch refill: the store still holds what
    # the two steps trained on, in batches of known padding, on the one rung
    # this job's budget has (16 prompt + 8 new tokens)
    want = []
    for batch in trainer._learner_loader():
        masks = [np.asarray(batch.query_mask), np.asarray(batch.response_mask)]
        assert [m.shape[1] for m in masks] == [16, 8]
        want.append(1.0 - sum(m.sum() for m in masks) / sum(m.size for m in masks))
    got = [r["learn/pad_frac"] for r in steps]
    assert sorted(got) == pytest.approx(sorted(want))
    assert all(0.0 < g < 1.0 for g in got)
    assert [r["learn/step_width"] for r in steps] == [24.0, 24.0]
    assert steps[-1]["learn/step_shapes"] == 1.0 and "recompile/train_step" not in steps[-1]
    # with time/train_step the gap tiles the learn phase: the second step's
    # gap is the host time from the first step's fence to its own launch. The
    # fence is the landing's (`learn/land` opens with it), and the job's
    # first step lands before anything else is launched
    first, second = sorted(_spans(run, "train_step"), key=lambda e: e["ts"])
    landed, _ = sorted(_spans(run, "learn/land"), key=lambda e: e["ts"])
    assert first["ts"] + first["dur"] <= landed["ts"] <= second["ts"]
    assert steps[1]["time/step_gap"] == pytest.approx(
        (second["ts"] - landed["ts"]) * 1e-6 - landed["args"]["wait_s"], abs=2e-4)
    assert [r["learn/ahead"] for r in steps] == [0.0, 0.0]


def test_known_padding_gives_the_known_fraction(run):
    batch = {
        "query_mask": np.ones((2, 4), np.int32),
        "response_mask": np.array([[1, 1, 0, 0], [1, 0, 0, 0]], np.int32),
        "rewards": np.zeros((2, 4), np.float32),
    }
    assert run["trainer"]._batch_token_counts(batch) == (11, 16, 8)
    assert run["trainer"]._batch_token_counts({"attention_mask": np.eye(3)}) == (3, 9, 3)


def test_spans_cover_the_cycle(run):
    names = {e["name"] for e in run["events"]}
    assert NEW_SPANS <= names, sorted(NEW_SPANS - names)
    inside = [e for e in run["events"] if e.get("ph") == "X" and e["name"] in NEW_SPANS
              or e["name"] in ("generate", "score", "reward", "train_step")]
    # every span opened after the first collection began says which cycle
    t_cycle = min(e["ts"] for e in _spans(run, "collect/experience"))
    assert all(e["args"]["cycle"] == 1 for e in inside if e["ts"] >= t_cycle)
    # the host gap never overlaps a step: it closes where the step's span opens
    hosts = _spans(run, "learn/step_host")
    for step in _spans(run, "train_step"):
        for h in hosts:
            assert h["ts"] + h["dur"] <= step["ts"] + 1e-3 or h["ts"] >= step["ts"] + step["dur"] - 1e-3
    # per-chunk work sits inside the collection's span
    (whole,) = _spans(run, "collect/experience")
    for name in ("collect/prompts", "collect/finalize", "generate"):
        for e in _spans(run, name, cycle=1):
            if e["name"] == "generate" and e["args"].get("eval_mode"):
                continue
            assert whole["ts"] <= e["ts"] and e["ts"] + e["dur"] <= whole["ts"] + whole["dur"] + 1e-3


def _module_names(run):
    """Module names of the programs this trainer built, from their lowered
    text (nothing compiles or runs)."""
    if "modules" in run:
        return run["modules"]
    trainer = run["trainer"]
    P, mask = run["generated"][0]
    B, N = mask.shape
    ids = np.zeros((B, P), np.int32)
    out = {}
    for fn in trainer._generate_fns.values():
        if not hasattr(fn, "lower"):  # the engine wrapper cached beside its program
            continue
        text = fn.lower(trainer.state.params, ids, ids, jax.random.PRNGKey(0)).as_text()
        out.setdefault("generate", []).append(re.search(r"module @(\S+)", text).group(1))
    for (b, p, n), fn in trainer._score_fns.items():
        text = fn.lower(
            trainer.state.params, trainer.ref_params, np.zeros((b, p + n), np.int32),
            np.zeros((b, p), np.int32), np.zeros((b, n), np.int32), np.zeros((b, n), np.int32),
        ).as_text()
        out.setdefault("score", []).append(re.search(r"module @(\S+)", text).group(1))
    batch = next(iter(trainer.store.create_loader(8)))
    arrays = shard_batch({k: v for k, v in batch._asdict().items() if hasattr(v, "ndim")},
                         trainer.mesh)
    text = trainer._train_step_fn.lower(trainer.state, arrays, trainer._loss_scale()).as_text()
    out["train"] = [re.search(r"module @(\S+)", text).group(1)]
    run["modules"] = out
    return out


def test_programs_have_stable_names(run):
    names = _module_names(run)
    assert set(names["generate"]) == {"jit_rollout_generate"}
    assert set(names["train"]) == {"jit_train_step"}
    assert set(names["score"]) == {"jit_score_fn"}
    everything = {n for group in names.values() for n in group}
    assert [n for n in everything if "score_fn" in n] == ["jit_score_fn"]


def test_flash_kernels_are_named_in_the_lowered_text():
    q = np.zeros((1, 16, 2, 8), np.float32)
    mask = np.ones((1, 16), np.float32)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, mask, interpret=True).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).as_text(debug_info=True)
    assert fa.FWD_KERNEL_NAME in text and fa.BWD_KERNEL_NAME in text
    assert fa.FWD_KERNEL_NAME != fa.BWD_KERNEL_NAME


@pytest.mark.parametrize("name", NEW_LAYER_METRICS)
def test_layer_metric_reads_what_the_program_emits(run, name):
    from chipbench import layers

    with open(os.path.join(REPO, "chipbench", "layer_metrics", f"{name}.json")) as f:
        spec = json.load(f)
    assert spec["name"] == name
    declared = {m["name"]: m for m in layers.job.load_benchmark()["per_layer"]}
    assert {k: spec[k] for k in ("unit", "better", "source", "layer", "moves")} == {
        k: declared[name][k] for k in ("unit", "better", "source", "layer", "moves")}
    reducers = set(re.findall(r'kind == "(\w+)"', inspect.getsource(layers.reduce_one)))
    assert spec["reducer"] in reducers
    if spec["reducer"].startswith("stat_"):
        on_steps = spec["reducer"] == "stat_median" or spec.get("where") == "steps"
        marker = "time/train_step" if on_steps else "time/exp"
        records = [r for r in run["records"] if marker in r]
        assert records and all(spec["key"] in r for r in records)
    elif spec["reducer"] == "trace_module_share":
        # an XLA Modules event reads "<module>(<fingerprint>)"
        events = [f"{n}(1234567890)" for group in _module_names(run).values() for n in group]
        assert sum(bool(re.search(spec["pattern"], e)) for e in events) == 1
    else:
        # an XLA Ops event is the instruction's text; the TPU compiler names
        # the two custom calls after pallas_call's name (tests/test_aot_tpu.py)
        assert spec["reducer"] == "trace_op_sum"
        events = [f'%{k}.7 = (bf16[8,16,1024,256]) custom-call(bf16[8] %{o}.7), '
                  'custom_call_target="tpu_custom_call"'
                  for k, o in ((fa.FWD_KERNEL_NAME, fa.BWD_KERNEL_NAME),
                               (fa.BWD_KERNEL_NAME, fa.FWD_KERNEL_NAME))]
        assert sum(bool(re.search(spec["pattern"], e)) for e in events) == 1


def test_host_gaps_cuts_idle_at_span_boundaries():
    from chipbench import host_gaps, trace

    ops = {"/device:TPU:0": [("%a = f32[] add()", 0.0, 1.0), ("%b = f32[] add()", 3.0, 1.0)]}
    spans = [("trlx/train_step", 0.0, 1.5), ("trlx/learn/step_host", 1.5, 1.0),
             ("trlx/learn/loader", 2.0, 0.25), ("trlx/train_step", 2.75, 1.25)]
    gaps = host_gaps.device_gaps(ops, (0.0, 4.0))
    assert gaps == [(1.0, 3.0)]
    sliced = host_gaps.idle_by_slice(gaps, host_gaps.innermost_timeline(spans))
    assert sliced == pytest.approx({
        "trlx/train_step": 0.75, "trlx/learn/step_host": 0.75, "trlx/learn/loader": 0.25,
        host_gaps.OUTSIDE: 0.25,
    })
    # the ledger's rule gives the whole gap to the span it began in
    assert dict(trace.idle_gaps(ops, spans, (0.0, 4.0))) == {"trlx/train_step": 2.0}
    modules = {"/device:TPU:0": [("jit_train_step(1)", 0.1, 0.9), ("jit_train_step(1)", 3.0, 1.5)]}
    # the second program outlasts its span: a fence that is not inside
    assert host_gaps.train_step_fences(spans, modules) == (1, 2)


# ---------------------------------------------------------------------------
# the learner's pad policy: length-grouped minibatches on a ladder of widths
# (trainer/ppo.py::_learner_loader; loader tests in tests/test_pipelines.py)
# ---------------------------------------------------------------------------

# byte prompts of uneven lengths: sorted into four minibatches of eight their
# longest rows are 11, 25, 39 and 60 tokens
UNEVEN = [3, 4, 5, 6, 7, 8, 9, 11, 12, 14, 16, 18, 20, 22, 24, 25,
          26, 28, 30, 32, 34, 36, 38, 39, 40, 44, 48, 52, 56, 58, 59, 60]


def _uneven_run(method, tmp_path, monkeypatch, total_steps, **model):
    """Two cycles of a toy job whose query budget is 64 tokens, with the
    ladder's smallest rung lowered from 128 to 16 for the toy widths: rungs
    16, 32 and 64 for the queries, 8 for the responses."""
    from trlx_tpu.pipeline import ppo_pipeline

    monkeypatch.setattr(ppo_pipeline, "LADDER_BASE", 16)
    default = default_grpo_config if method == "grpo" else default_ppo_config
    lengths = UNEVEN[::4] if method == "grpo" else UNEVEN  # 8 prompts x group 4
    config = default().evolve(
        train=dict(
            seq_length=72, batch_size=8, total_steps=total_steps, eval_interval=100,
            checkpoint_interval=100, epochs=2, save_best=False, tracker=None,
            checkpoint_dir=str(tmp_path / "ckpts"), logging_dir=str(tmp_path / "logs"),
        ),
        model=dict(model_path="builtin:gpt2-test", num_layers_unfrozen=1, **model),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        method=dict(
            num_rollouts=32, chunk_size=len(lengths), ppo_epochs=1,
            gen_kwargs=dict(max_new_tokens=8, min_new_tokens=8, top_k=0, top_p=1.0,
                            do_sample=True),
            **(dict(group_size=4) if method == "grpo" else {}),
        ),
    )
    recorder = Recorder()

    def hook(trainer):
        trainer.tracker = recorder

    def reward_fn(samples, prompts, outputs, **kwargs):
        return [float(len(o)) + 0.1 * i for i, o in enumerate(outputs)]

    rng = np.random.RandomState(0)
    prompts = ["".join(chr(97 + c) for c in rng.randint(0, 26, size=n)) for n in lengths]
    trainer = trlx.train(reward_fn=reward_fn, prompts=prompts, config=config,
                         init_trainer_hook=hook)
    return trainer, [r for r in recorder.records if "time/train_step" in r]


@pytest.mark.parametrize("method", ["ppo", "grpo"])
def test_uneven_rows_compile_one_train_step_per_ladder_shape(method, tmp_path, monkeypatch):
    """No outside pin: the trainer's own loader keeps every minibatch on
    ladder x ladder, so two cycles of uneven rows compile one train step per
    shape, none of them counted as a recompile."""
    trainer, steps = _uneven_run(method, tmp_path, monkeypatch, total_steps=8)
    assert trainer._step_ladders == ((16, 32, 64), (8,))
    assert len(steps) == 8
    widths = [r["learn/step_width"] for r in steps]
    assert sorted(widths[:4]) == sorted(widths[4:]) == [24.0, 40.0, 72.0, 72.0]
    shapes = len(set(widths))
    assert shapes == 3
    assert trainer._train_step_fn._cache_size() == shapes
    assert steps[-1]["learn/step_shapes"] == float(shapes)
    assert [r["learn/step_shapes"] for r in steps[4:]] == [3.0] * 4  # the second cycle adds none
    assert all(r.get("recompile/train_step", 0.0) == 0.0 for r in steps)
    assert trainer.obs.recompile.excess_compiles("train_step") == 0
    # grouped rows waste less: the uniform partition would pad every step to 72
    assert np.mean([r["learn/pad_frac"] for r in steps]) < 0.45


def test_loss_and_gradients_do_not_depend_on_the_padded_width(tmp_path, monkeypatch):
    """PPO with value head and hydra branch, float32: the same eight rows
    collated at the rung they need (32) and at the job's maximum (64, what
    the benchmark's pin fed every step) give one loss, one set of stats and
    one set of gradients."""
    trainer, _ = _uneven_run("ppo", tmp_path, monkeypatch, total_steps=1,
                             model_extra_kwargs={"dtype": "float32"})
    assert trainer.num_layers_unfrozen == 1 and "v_head" in str(
        jax.tree_util.tree_structure(trainer.state.params))
    rows = sorted(trainer.store.history, key=lambda e: len(e.query_tensor))[8:16]
    assert max(len(e.query_tensor) for e in rows) == 25

    @jax.jit
    def value_and_grad(params, batch):
        (loss, stats), grads = jax.value_and_grad(trainer.loss_fn, has_aux=True)(
            params, batch, jax.random.PRNGKey(0))
        return loss, stats, grads

    results = []
    for width in ((16, 32, 64), 64):
        batch = trainer.store.collate(rows, query_length=width, response_length=(8,))
        assert batch.query_tensors.shape == (8, 32 if width != 64 else 64)
        arrays = shard_batch({k: v for k, v in batch._asdict().items() if v is not None},
                             trainer.mesh)
        results.append(jax.device_get(value_and_grad(trainer.state.params, arrays)))
    (loss_a, stats_a, grads_a), (loss_b, stats_b, grads_b) = results
    assert np.isfinite(loss_a) and loss_a == pytest.approx(loss_b, abs=1e-5)
    assert stats_a.keys() == stats_b.keys()
    for key in stats_a:
        np.testing.assert_allclose(stats_a[key], stats_b[key], atol=1e-5, err_msg=key)
    flat_a, flat_b = (jax.tree_util.tree_leaves(g) for g in (grads_a, grads_b))
    assert any(np.abs(g).max() > 1e-4 for g in flat_a)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(a, b, atol=1e-5)
