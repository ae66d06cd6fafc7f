"""Which rows share a scoring forward (``trainer/ppo.py::score_groups``,
docs/PERFORMANCE.md): a chunk whose rows need different rungs is scored in
length-sorted groups of ``train.batch_size`` rows, each with the left padding
beyond its rung cut off; a chunk whose rows all need the chunk's own width is
scored whole, in the one program it always had. The rungs are the first and
the last of the learner's query ladder (``score_rungs``).

Toy jobs in float32 with the ladder's smallest rung lowered to 16: a query
budget of 64 gives the ladder 16, 32, 64, of which scoring uses 16 and 64.
"""

import json
import os

import numpy as np
import pytest

import trlx_tpu.pipeline.offline_pipeline  # noqa: F401 (registration)
import trlx_tpu.trainer.grpo  # noqa: F401 (registration)
import trlx_tpu.trainer.ppo  # noqa: F401 (registration)
from trlx_tpu.data.default_configs import default_grpo_config, default_ppo_config
from trlx_tpu.pipeline import get_pipeline, ppo_pipeline
from trlx_tpu.pipeline.ppo_pipeline import length_ladder
from trlx_tpu.trainer import get_trainer
from trlx_tpu.trainer.ppo import score_groups, score_rungs, scores_in_chunk_order
from trlx_tpu.utils import to_host

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
P, N = 64, 8
# sorted into four groups of two the longest rows are 9, 20, 33 and 60 tokens: rungs 16, 64, 64, 64
LENGTHS = [5, 9, 14, 20, 27, 33, 41, 60]
ORDERS = {
    "sorted": [0, 1, 2, 3, 4, 5, 6, 7],
    "reversed": [7, 6, 5, 4, 3, 2, 1, 0],
    "mixed": [3, 7, 0, 5, 1, 6, 2, 4],
}


def _trainer(method, tmp_path, monkeypatch, batch_size=2, seq_length=P + N, new=N, base=16):
    monkeypatch.setattr(ppo_pipeline, "LADDER_BASE", base)
    default = default_grpo_config if method == "grpo" else default_ppo_config
    config = default().evolve(
        train=dict(
            seq_length=seq_length, batch_size=batch_size, total_steps=4, checkpoint_interval=1000,
            checkpoint_dir=str(tmp_path / "ckpts"), tracker=None, rollout_pipeline_depth=0,
        ),
        model=dict(model_path="builtin:gpt2-test", num_layers_unfrozen=1),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        parallel=dict(param_dtype="float32", compute_dtype="float32"),
        method=dict(
            num_rollouts=8, chunk_size=8, ppo_epochs=1,
            gen_kwargs=dict(max_new_tokens=new, min_new_tokens=new, top_k=0, top_p=1.0, do_sample=True),
            **(dict(group_size=2) if method == "grpo" else {}),
        ),
    )
    return get_trainer(config.train.trainer)(
        config=config, reward_fn=lambda samples, prompts, outputs, **kw: [float(len(o)) for o in outputs],
        metric_fn=None, stop_sequences=[],
    )


def _chunk(lengths, width=P, new=N, seed=0):
    """A left-padded chunk on the host: ids, mask, response tokens and a
    response mask whose rows end at different lengths."""
    rng = np.random.RandomState(seed)
    B = len(lengths)
    ids = np.full((B, width), 258, np.int32)
    mask = np.zeros((B, width), np.int32)
    for i, n in enumerate(lengths):
        ids[i, width - n:] = rng.randint(97, 123, size=n)
        mask[i, width - n:] = 1
    tokens = rng.randint(97, 123, size=(B, new)).astype(np.int32)
    tmask = (np.arange(new)[None, :] < rng.randint(new // 2, new + 1, size=(B, 1))).astype(np.int32)
    return ids, mask, tokens, tmask


def _score(trainer, chunk, grouped):
    ids, mask, tokens, tmask = chunk
    out = trainer._dispatch_score(
        (ids.shape[0], ids.shape[1], tokens.shape[1]), np.concatenate([ids, tokens], axis=1), mask,
        tokens, tmask, prompt_ids=ids if grouped else None,
    )
    return out, scores_in_chunk_order(to_host(out))


@pytest.mark.parametrize("order", sorted(ORDERS))
@pytest.mark.parametrize("method", ["ppo", "grpo"])
def test_grouped_scores_equal_the_whole_width_scores_row_for_row(method, order, tmp_path, monkeypatch):
    """(a) PPO with value head and hydra branch, GRPO without a head: the
    groups' logprobs, reference logprobs and values are the whole chunk's to
    1e-5, in the chunk's own row order, whatever that order is."""
    trainer = _trainer(method, tmp_path, monkeypatch)
    chunk = _chunk([LENGTHS[i] for i in ORDERS[order]])
    _, whole = _score(trainer, chunk, grouped=False)
    raw, grouped = _score(trainer, chunk, grouped=True)
    assert sorted(trainer._score_fns) == [(2, 16, N), (2, 64, N), (8, 64, N)]
    assert [len(t) for t in raw["takes"]] == [2, 2, 2, 2] and len(raw["groups"]) == 4
    assert sorted(whole) == sorted(grouped) == (
        ["logprobs", "ref_logprobs", "values"] if method == "ppo" else ["logprobs", "ref_logprobs"]
    )
    tmask = chunk[3].astype(bool)
    for key in whole:
        assert grouped[key].shape == whole[key].shape == (8, N)
        np.testing.assert_allclose(grouped[key][tmask], whole[key][tmask], atol=1e-5, rtol=0, err_msg=key)
    # the reference is another function of the row than the policy: the order matters to the check
    assert np.abs(whole["logprobs"][0] - whole["logprobs"][1]).max() > 1e-3


@pytest.mark.parametrize("length,width", [(64, 64), (24, 24), (40, 64)])
def test_rows_of_one_rung_dispatch_once_under_the_chunks_own_key(length, width, tmp_path, monkeypatch):
    """(b) the guarantee of the cells whose prompts have one length: one
    dispatch, the program keyed (B, P, N), nothing else built. A chunk
    narrower than its rung (24 under rung 64) is no exception; nor is one
    whose rows all need the top rung though none fills it (40 in 64)."""
    trainer = _trainer("ppo", tmp_path, monkeypatch)
    out, _ = _score(trainer, _chunk([length] * 8, width=width), grouped=True)
    assert list(trainer._score_fns) == [(8, width, N)]
    assert "groups" not in out and out["logprobs"].shape == (8, N)
    assert trainer._score_groups(_chunk([length] * 8, width=width)[1]) is None


def test_a_chunk_that_is_no_whole_number_of_groups_dispatches_once(tmp_path, monkeypatch):
    """(e) eight rows under a minibatch of three: whole, as it always was."""
    trainer = _trainer("ppo", tmp_path, monkeypatch, batch_size=3)
    out, _ = _score(trainer, _chunk(LENGTHS), grouped=True)
    assert list(trainer._score_fns) == [(8, P, N)] and "groups" not in out
    assert score_groups(LENGTHS, P, 3, (16, 32, 64)) is None
    assert score_groups(LENGTHS, P, 0, (16, 32, 64)) is None
    assert score_groups(LENGTHS, P, 2, ()) is None  # a job that states no length budget has no ladder


def test_scoring_uses_the_first_and_last_rungs_of_the_learners_ladder():
    assert score_rungs((256, 512, 896)) == (256, 896)
    assert score_rungs((256, 512, 1024, 2048)) == (256, 2048)
    assert score_rungs((256, 896)) == (256, 896) and score_rungs((128,)) == (128,) and score_rungs(()) == ()


def test_groups_are_sorted_by_length_stably_and_never_wider_than_the_chunk():
    groups = score_groups([20, 5, 20, 60, 5, 33, 9, 41], 64, 2, (16, 32, 64))
    assert [(take.tolist(), width) for take, width in groups] == [
        ([1, 4], 16), ([6, 0], 32), ([2, 5], 64), ([7, 3], 64),
    ]
    # a chunk narrower than a group's rung: that group keeps the chunk's width
    groups = score_groups([5, 9, 33, 40], 40, 2, (16, 32, 64))
    assert [(take.tolist(), width) for take, width in groups] == [([0, 1], 16), ([2, 3], 40)]
    # a prompt over the top rung (over the job's own budget): the same
    groups = score_groups([5, 9, 33, 70], 72, 2, (16, 32, 64))
    assert [(take.tolist(), width) for take, width in groups] == [([0, 1], 16), ([2, 3], 72)]


class Recorder:
    def __init__(self):
        self.records = []

    def log(self, stats, step=None):
        self.records.append(dict(stats))

    def finish(self):
        pass


@pytest.mark.parametrize("depth", [0, 2])
def test_three_collections_build_one_program_a_rung_and_none_again(depth, tmp_path, monkeypatch):
    """(c) shuffled chunks of one multiset of lengths: the scoring forward
    has a program a rung and the watchdog sees no second compile of any."""
    trainer = _trainer("ppo", tmp_path, monkeypatch)
    trainer.config.train.rollout_pipeline_depth = depth
    trainer.tracker = Recorder()
    rng = np.random.RandomState(1)
    prompts = ["".join(chr(97 + c) for c in rng.randint(0, 26, size=n)) for n in LENGTHS]
    trainer.add_prompt_pipeline(get_pipeline(trainer.config.train.pipeline)(prompts, P, trainer.tokenizer))
    orders = []
    for _ in range(3):
        trainer.store.clear_history()
        trainer.make_experience(8)
        orders.append([len(e.query_tensor) for e in trainer.store.history])
        assert len(trainer._score_fns) <= len(score_rungs(length_ladder(P)))
    assert all(sorted(o) == LENGTHS for o in orders) and len({tuple(o) for o in orders}) > 1
    assert sorted(trainer._score_fns) == [(2, 16, N), (2, 64, N)]
    assert all(fn._cache_size() == 1 for fn in trainer._score_fns.values())
    assert trainer.obs.recompile.excess_compiles("score") == 0
    records = [r for r in trainer.tracker.records if "time/exp" in r]
    assert [r["collect/score_shapes"] for r in records] == [2.0, 2.0, 2.0]
    # 8 rows x 8 new tokens, all real, beside 209 prompt tokens in 2 x (24 + 72 + 72 + 72) slots
    want = 1.0 - (sum(LENGTHS) + 64) / 480
    assert [r["collect/score_pad_frac"] for r in records] == pytest.approx([want] * 3)


def _hh_masks():
    from chipbench import job

    with open(os.path.join(REPO, "chipbench", "traffic", "ppo_hh.json")) as f:
        traffic = json.load(f)
    lengths = job.prompt_lengths(traffic["prompt_length"], traffic["prompts_per_cycle"])
    width, new = max(lengths), traffic["max_new_tokens"]
    mask = (np.arange(width)[None, :] >= width - np.asarray(lengths)[:, None]).astype(np.int32)
    return lengths, mask, np.ones((len(lengths), new), np.int32)


@pytest.mark.parametrize("batch_size,want", [(8, 0.3633), (5, 0.5623)])
def test_pad_fraction_of_the_hh_multiset(batch_size, want, tmp_path, monkeypatch):
    """(d) the two cells' own chunk (32 prompts of 64 to 896 tokens, 128 new):
    22,528 slots at the rungs 256 and 896 where the whole chunk is 32,768, for
    14,344 real tokens (the whole ladder 256, 512, 896 would give 19,456 slots,
    0.2627, for one more program: ``score_rungs``)."""
    lengths, mask, tmask = _hh_masks()
    assert (len(lengths), sum(lengths), mask.shape, tmask.shape) == (32, 10248, (32, 896), (32, 128))
    groups = score_groups(lengths, 896, 8, length_ladder(896))
    assert [width for _, width in groups] == [256, 256, 512, 896]
    assert sum(len(take) * (width + 128) for take, width in groups) == 19456
    groups = score_groups(lengths, 896, 8, score_rungs(length_ladder(896)))
    assert [width for _, width in groups] == [256, 256, 896, 896]
    assert sum(len(take) * (width + 128) for take, width in groups) == 22528

    trainer = _trainer("ppo", tmp_path, monkeypatch, batch_size=batch_size, seq_length=1024, new=128, base=256)
    acc, stats = {}, {}
    trainer._note_score_slots({"prompt_mask": mask, "response_mask": tmask}, acc)
    trainer._score_summary(stats, acc)
    assert acc["score_tokens"] == 14344 and acc["score_slots"] == (22528 if batch_size == 8 else 32768)
    assert stats["collect/score_pad_frac"] == pytest.approx(want, abs=5e-5)
    assert stats["collect/score_shapes"] == 0.0  # nothing was dispatched


def test_pad_fraction_of_full_rows_is_zero(tmp_path, monkeypatch):
    trainer = _trainer("ppo", tmp_path, monkeypatch, batch_size=8, seq_length=640, new=512, base=256)
    acc, stats = {}, {}
    chunk = {"prompt_mask": np.ones((64, 128), np.int32), "response_mask": np.ones((64, 512), np.int32)}
    trainer._note_score_slots(chunk, acc)
    trainer._score_summary(stats, acc)
    assert stats["collect/score_pad_frac"] == 0.0


def test_the_metric_file_reads_the_collection_records_key():
    with open(os.path.join(REPO, "chipbench", "layer_metrics", "score_pad_pct.json")) as f:
        spec = json.load(f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == "score_pad_pct"]
    assert (spec["reducer"], spec["key"], spec["scale"]) == ("stat_mean", "collect/score_pad_frac", 100.0)
    assert {k: spec[k] for k in ("unit", "better", "layer", "moves", "source")} == {
        k: entry[k] for k in ("unit", "better", "layer", "moves", "source")
    }
    assert "workloads" not in entry  # every cell's collection record has the key

    from chipbench import layers

    class H:
        cycles = [{"collection": {"collect/score_pad_frac": 0.25}}, {"collection": {"collect/score_pad_frac": 0.27}}]

    assert layers.reduce_one(spec, H, None, None, 1) == pytest.approx(26.0)
    H.cycles = [{"collection": {}}]  # a program without the counter: the metric is left out
    assert layers.reduce_one(spec, H, None, None, 1) is None
