"""Data-layer tests (shape of the reference's ``tests/test_pipelines.py``:
property-based checks of dialogue tokenization + collation)."""

import numpy as np
import pytest

# optional dev dependency (pyproject [dev] extra): without the guard this
# module fails COLLECTION and tier-1 needs --continue-on-collection-errors
pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from trlx_tpu.data.ppo_types import PPORLElement
from trlx_tpu.data.tokenizer import ByteTokenizer, CharTokenizer, from_config
from trlx_tpu.data.configs import TokenizerConfig
from trlx_tpu.models.sft import IGNORE_INDEX
from trlx_tpu.pipeline.offline_pipeline import (
    DialogStore,
    PromptPipeline,
    pad_rows,
    round_up,
    tokenize_dialogue,
)
from trlx_tpu.pipeline.ppo_pipeline import PPORolloutStorage

TEXT = st.text(
    alphabet=st.characters(codec="utf-8", exclude_characters=["<"]), min_size=0, max_size=40
)


@given(TEXT)
@settings(max_examples=50, deadline=None)
def test_byte_tokenizer_roundtrip(text):
    tok = ByteTokenizer()
    assert tok.decode(tok.encode(text)) == text


def test_byte_tokenizer_specials():
    tok = ByteTokenizer()
    ids = tok.encode(f"hi{tok.eos_token}")
    assert ids[-1] == tok.eos_token_id
    assert tok.decode(ids) == "hi"
    assert tok.decode(ids, skip_special_tokens=False).endswith(tok.eos_token)


def test_char_tokenizer():
    tok = CharTokenizer("abcd")
    assert tok.encode("abba") == [0, 1, 1, 0]
    assert tok.decode([3, 2]) == "dc"
    with pytest.raises(ValueError):
        tok.encode("xyz")


def test_from_config_builtin():
    assert isinstance(from_config(TokenizerConfig("builtin:bytes")), ByteTokenizer)
    tok = from_config(TokenizerConfig("builtin:chars:xyz"))
    assert isinstance(tok, CharTokenizer) and tok.vocab_size == 6


@given(TEXT.filter(bool))
@settings(max_examples=25, deadline=None)
def test_tokenize_dialogue_single_string(text):
    tok = ByteTokenizer()
    msgs = tokenize_dialogue(text, tok, max_length=1024)
    # bos prompt turn + output turn ending in eos
    assert msgs[0].is_output is False
    assert msgs[-1].is_output is True
    assert msgs[-1].tokens[-1] == tok.eos_token_id
    flat = [t for m in msgs if m.is_output for t in m.tokens]
    assert tok.decode(flat) == text


@given(st.integers(min_value=2, max_value=30))
@settings(max_examples=25, deadline=None)
def test_tokenize_dialogue_truncation_right(max_length):
    tok = ByteTokenizer(truncation_side="right")
    tok.truncation_side = "right"
    msgs = tokenize_dialogue(["user: " + "a" * 30, "bot: " + "b" * 30], tok, max_length)
    total = sum(len(m.tokens) for m in msgs)
    assert total <= max_length
    # right truncation keeps the beginning
    first = msgs[0]
    assert first.tokens[0] == tok.encode("u")[0]


@given(st.integers(min_value=2, max_value=30))
@settings(max_examples=25, deadline=None)
def test_tokenize_dialogue_truncation_left(max_length):
    tok = ByteTokenizer(truncation_side="left")
    msgs = tokenize_dialogue(["user: " + "a" * 30, "bot: " + "b" * 30], tok, max_length)
    total = sum(len(m.tokens) for m in msgs)
    assert total <= max_length
    # left truncation keeps the end (eos)
    assert msgs[-1].tokens[-1] == tok.eos_token_id


def test_tokenize_dialogue_multiturn_and_odd_raises():
    tok = ByteTokenizer()
    msgs = tokenize_dialogue(["q1", "a1", "q2", "a2"], tok, max_length=100)
    assert [m.is_output for m in msgs] == [False, True, False, True]
    with pytest.raises(ValueError):
        tokenize_dialogue(["only", "two", "three"], tok, max_length=100)


def test_dialog_store_masks_prompt_tokens():
    tok = ByteTokenizer()
    dialogs = [tokenize_dialogue(["ab", "cd"], tok, max_length=64)]
    store = DialogStore(dialogs, tok)
    loader = store.create_loader(batch_size=1, pad_multiple=8)
    batch = next(iter(loader))
    labels, ids = batch["labels"][0], batch["input_ids"][0]
    n_prompt = 2
    assert (labels[:n_prompt] == IGNORE_INDEX).all()
    # output segment labels match ids
    out_region = (labels != IGNORE_INDEX) & (batch["attention_mask"][0] > 0)
    assert (labels[out_region] == ids[out_region]).all()
    assert ids.shape[0] % 8 == 0


def test_prompt_pipeline_truncates_and_left_pads():
    tok = ByteTokenizer()
    pipeline = PromptPipeline(["x" * 50, "short"], max_prompt_length=10, tokenizer=tok)
    assert len(pipeline[0]["input_ids"]) == 10
    loader = pipeline.create_loader(batch_size=2, pad_multiple=8)
    batch = next(iter(loader))
    assert batch["input_ids"].shape == (2, 16)
    # left padding: real tokens at the end
    assert batch["attention_mask"][1][-5:].all()
    assert (batch["input_ids"][1][:-5] == tok.pad_token_id).all()
    assert batch["text"] == ["x" * 50, "short"]


def test_pad_rows_bucketing():
    assert round_up(1, 8) == 8
    assert round_up(8, 8) == 8
    assert round_up(9, 8) == 16
    out, mask = pad_rows([[1, 2, 3], [1]], 0, "right", 8)
    assert out.shape == (2, 8)
    assert mask.sum() == 4
    out, _ = pad_rows([[1, 2, 3]], 0, "right", 8, fixed_length=32)
    assert out.shape == (1, 32)


def _fake_element(q, r, seed=0):
    rng = np.random.RandomState(seed)
    return PPORLElement(
        query_tensor=np.arange(q, dtype=np.int32),
        response_tensor=np.arange(r, dtype=np.int32) + 100,
        logprobs=rng.randn(r).astype(np.float32),
        values=rng.randn(r).astype(np.float32),
        rewards=rng.randn(r).astype(np.float32),
    )


def test_ppo_rollout_storage_collate():
    store = PPORolloutStorage(pad_token_id=0)
    store.push([_fake_element(3, 5), _fake_element(6, 2)])
    loader = store.create_loader(batch_size=2, pad_multiple=8)
    batch = next(iter(loader))
    assert batch.query_tensors.shape == (2, 8)
    assert batch.response_tensors.shape == (2, 8)
    assert batch.logprobs.shape == (2, 8)
    # queries left-padded, responses right-padded
    assert batch.query_mask[0][-3:].all() and not batch.query_mask[0][:5].any()
    assert batch.response_mask[0][:5].all() and not batch.response_mask[0][5:].any()
    # clear_history empties
    store.clear_history()
    assert len(store) == 0


def test_ppo_rollout_storage_export(tmp_path):
    store = PPORolloutStorage(pad_token_id=0)
    store.push([_fake_element(2, 3)])
    store.export_history(str(tmp_path))
    import glob, json

    files = glob.glob(str(tmp_path / "*.json"))
    assert len(files) == 1
    data = json.load(open(files[0]))
    assert data[0]["query_tensor"] == [0, 1]


def test_rollout_storage_export_appends_fresh_ordinal(tmp_path):
    # ordinal naming: the second export lands beside the first, never over
    # it (full determinism coverage lives in tests/test_utils.py, which
    # collects without hypothesis)
    store = PPORolloutStorage(pad_token_id=0)
    store.push([_fake_element(2, 3)])
    store.export_history(str(tmp_path))
    store.export_history(str(tmp_path))
    import glob

    assert len(glob.glob(str(tmp_path / "epoch-*.json"))) == 2


def test_ilql_collate_shapes():
    from trlx_tpu.data.ilql_types import ILQLElement
    from trlx_tpu.pipeline.offline_pipeline import ilql_collate

    def elem(t, a):
        return ILQLElement(
            input_ids=np.arange(t, dtype=np.int32),
            attention_mask=np.ones(t, dtype=np.int32),
            rewards=np.zeros(a, dtype=np.float32),
            states_ixs=np.arange(a + 1, dtype=np.int32),
            actions_ixs=np.arange(a, dtype=np.int32),
            dones=np.ones(a + 1, dtype=np.int32),
        )

    batch = ilql_collate([elem(10, 4), elem(6, 2)], pad_multiple=8)
    assert batch.input_ids.shape == (2, 16)
    assert batch.rewards.shape == (2, 8)
    assert batch.actions_ixs.shape == (2, 8)
    assert batch.states_ixs.shape == (2, 9)
    assert batch.dones.shape == (2, 9)


def test_prefetch_loader_order_and_exceptions():
    """PrefetchLoader preserves batch order/content, is re-iterable, and
    re-raises worker exceptions in the consumer (the torch DataLoader
    prefetch analogue, SURVEY.md §2.4)."""
    import numpy as np
    import pytest

    from trlx_tpu.pipeline import BatchLoader, PrefetchLoader

    data = list(range(23))
    loader = BatchLoader(data, 4, collate_fn=lambda xs: np.asarray(xs), shuffle=True, seed=7)
    plain = [b.tolist() for b in loader]
    # fresh loader with same seed: prefetch must reproduce the same epochs
    loader2 = BatchLoader(data, 4, collate_fn=lambda xs: np.asarray(xs), shuffle=True, seed=7)
    pf = PrefetchLoader(loader2, depth=3)
    assert len(pf) == len(loader2)
    assert [b.tolist() for b in pf] == plain
    # second epoch: different shuffle, still equal between the two
    assert [b.tolist() for b in pf] == [b.tolist() for b in loader]

    class Boom:
        def __len__(self):
            return 1

        def __iter__(self):
            raise RuntimeError("collate exploded")

    with pytest.raises(RuntimeError, match="collate exploded"):
        list(PrefetchLoader(Boom()))
    with pytest.raises(ValueError):
        PrefetchLoader([], depth=0)


def test_prefetch_loader_early_stop():
    """Abandoning iteration mid-epoch must not deadlock the worker."""
    import numpy as np

    from trlx_tpu.pipeline import BatchLoader, PrefetchLoader

    loader = BatchLoader(list(range(100)), 2, collate_fn=lambda xs: np.asarray(xs))
    pf = PrefetchLoader(loader, depth=2)
    it = iter(pf)
    next(it), next(it)
    del it  # generator close → finally drains the queue
    # a fresh epoch still works
    assert len(list(pf)) == 50


def test_prefetch_loader_cancels_promptly():
    """Abandoning a long epoch cancels the worker between batches instead of
    collating the rest of the epoch into a drain loop (review regression)."""
    import time

    import numpy as np

    from trlx_tpu.pipeline import BatchLoader, PrefetchLoader

    collated = []

    def slow_collate(xs):
        collated.append(xs)
        time.sleep(0.01)
        return np.asarray(xs)

    loader = BatchLoader(list(range(4000)), 1, collate_fn=slow_collate)
    it = iter(PrefetchLoader(loader, depth=2))
    next(it)
    t0 = time.time()
    it.close()  # generator close runs the finally: must cancel, not drain
    assert time.time() - t0 < 2.0
    assert len(collated) < 50  # worker stopped early, not 4000 collations


# ---------------------------------------------------------------------------
# the rollout stores' pad policy: length-grouped minibatches on a ladder of
# widths (PPORolloutStorage.create_loader with ladders; the PPO and GRPO
# learners pass them, trainer/ppo.py::_learner_loader)
# ---------------------------------------------------------------------------

# one cycle of the ``ppo_hh`` traffic: 32 query lengths on a geometric grid
HH_LENGTHS = [int(round(x / 8.0)) * 8 for x in np.geomspace(64, 896, 32)]
QUERY_LADDER, RESPONSE_LADDER = (256, 512, 896), (128,)


def _rollout_store(kind, query_lengths, response_length=128, order_seed=0):
    """A PPO or GRPO store whose row ``i`` carries ``i`` as its first
    response token, in an order drawn from ``order_seed``."""
    from trlx_tpu.data.grpo_types import GRPORLElement
    from trlx_tpu.pipeline.grpo_pipeline import GRPORolloutStorage

    lengths = list(query_lengths)
    np.random.RandomState(order_seed).shuffle(lengths)
    store = (GRPORolloutStorage if kind == "grpo" else PPORolloutStorage)(pad_token_id=0)
    rows = []
    for i, q in enumerate(lengths):
        response = np.full(response_length, 7, np.int32)
        response[0] = 1000 + i
        per_token = np.zeros(response_length, np.float32)
        common = dict(query_tensor=np.full(q, 5, np.int32), response_tensor=response,
                      logprobs=per_token)
        if kind == "grpo":
            rows.append(GRPORLElement(ref_logprobs=per_token, advantage=float(i), **common))
        else:
            rows.append(PPORLElement(values=per_token, rewards=per_token, **common))
    store.push(rows)
    return store


def _ids(batch):
    return [int(t) - 1000 for t in np.asarray(batch.response_tensors)[:, 0]]


def _ladder_loader(store, batch_size=8, seed=0, **kw):
    return store.create_loader(batch_size, shuffle=True, seed=seed,
                               query_length=QUERY_LADDER, response_length=RESPONSE_LADDER, **kw)


def test_length_ladder_follows_the_budget():
    from trlx_tpu.pipeline.ppo_pipeline import length_ladder, pad_length

    assert length_ladder(896) == QUERY_LADDER and length_ladder(128) == RESPONSE_LADDER
    assert length_ladder(512) == (256, 512) and length_ladder(2048) == (256, 512, 1024, 2048)
    assert length_ladder(100) == (104,)
    assert length_ladder(0) == () and length_ladder(-8) == ()
    rows = [[0] * 260, [0] * 7]
    assert pad_length(rows, QUERY_LADDER) == 512
    assert pad_length(rows, 64) == 64 and pad_length(rows, None) is None
    assert pad_length([[0] * 900], QUERY_LADDER) is None  # over the budget: not cut


@pytest.mark.parametrize("kind", ["ppo", "grpo"])
@pytest.mark.parametrize("seed", [0, 3141592653])
def test_grouped_loader_feeds_every_row_once_on_the_ladder(kind, seed):
    store = _rollout_store(kind, HH_LENGTHS, order_seed=seed)
    batches = list(_ladder_loader(store, seed=seed))
    assert len(batches) == 4
    assert sorted(i for b in batches for i in _ids(b)) == list(range(32))
    widths = []
    for b in batches:
        assert b.query_tensors.shape[1] in QUERY_LADDER
        assert b.response_tensors.shape == (8, 128) == b.logprobs.shape
        # left-padded, nothing cut: every row keeps its own length
        assert sorted(np.asarray(b.query_mask).sum(axis=1)) == sorted(
            len(store[i].query_tensor) for i in _ids(b))
        assert np.asarray(b.query_mask)[:, -1].all()
        widths.append(b.query_tensors.shape[1])
    # the four sorted minibatches' longest queries are 120, 232, 456, 896
    assert sorted(widths) == [256, 256, 512, 896]
    slots = sum(8 * (w + 128) for w in widths)
    assert slots == 19456 and slots < 0.6 * 32 * 1024


@pytest.mark.parametrize("kind", ["ppo", "grpo"])
def test_grouped_loader_visits_widths_in_an_order_drawn_from_the_seed(kind):
    store = _rollout_store(kind, HH_LENGTHS)
    orders = {tuple(b.query_tensors.shape[1] for b in _ladder_loader(store, seed=s))
              for s in range(12)}
    assert len(orders) > 3  # not short to long, and not one fixed order
    assert all(sorted(o) == [256, 256, 512, 896] for o in orders)


@pytest.mark.parametrize("kind", ["ppo", "grpo"])
def test_rows_of_one_length_give_the_ungrouped_batches(kind):
    """Cells 1 and 3: every row on one rung, so the batches are the parent's
    (the uniform partition at the pinned widths), element for element and in
    order, epoch after epoch."""
    store = _rollout_store(kind, [128] * 64, response_length=512)
    from trlx_tpu.pipeline.ppo_pipeline import length_ladder

    assert (length_ladder(128), length_ladder(512)) == ((128,), (256, 512))
    grouped = store.create_loader(16, shuffle=True, seed=11, query_length=length_ladder(128),
                                  response_length=length_ladder(512))
    parent = store.create_loader(16, shuffle=True, seed=11, query_length=128,
                                 response_length=512)
    for _ in range(3):
        got, want = list(grouped), list(parent)
        assert len(got) == len(want) == 4
        for g, w in zip(got, want):
            for a, b in zip(g, w):
                assert (a is None and b is None) or (
                    a.shape == b.shape and a.dtype == b.dtype and (a == b).all())


@pytest.mark.parametrize("kind", ["ppo", "grpo"])
def test_grouped_partition_is_a_function_of_seed_and_store(kind):
    store = _rollout_store(kind, HH_LENGTHS)
    first, second = _ladder_loader(store, seed=5), _ladder_loader(store, seed=5)
    epochs = [[_ids(b) for b in first] for _ in range(2)]
    assert [_ids(b) for b in second] == epochs[0]
    assert epochs[0] != epochs[1]  # a fresh shuffle every epoch
    # emergency resume skips an epoch without iterating it
    resumed = _ladder_loader(store, seed=5)
    resumed.advance_epoch()
    assert [_ids(b) for b in resumed] == epochs[1]
    assert [_ids(b) for b in _ladder_loader(store, seed=6)] != epochs[0]


@pytest.mark.parametrize("kind", ["ppo", "grpo"])
@pytest.mark.parametrize("drop_last", [True, False])
def test_grouped_loader_keeps_drop_last(kind, drop_last):
    """The rows that do not fill a batch are the ones the uniform loader
    leaves over (the tail of the shuffle), not the longest."""
    store = _rollout_store(kind, HH_LENGTHS + [72, 600, 896])
    grouped = _ladder_loader(store, seed=2, drop_last=drop_last)
    uniform = store.create_loader(8, shuffle=True, seed=2, drop_last=drop_last)
    got, want = [_ids(b) for b in grouped], [_ids(b) for b in uniform]
    assert len(got) == len(want) == len(grouped) == (4 if drop_last else 5)
    assert sorted(i for b in got for i in b) == sorted(i for b in want for i in b)
    if not drop_last:
        assert got[-1] == want[-1] and len(got[-1]) == 3


@pytest.mark.parametrize("kind", ["ppo", "grpo"])
def test_a_callers_default_lengths_do_not_undo_the_policy(kind):
    """What ``chipbench/run.py::_pin_learner_pad`` does: ``setdefault`` of both
    lengths around ``create_loader``. The trainer passes its ladders by name,
    so the defaults never apply."""
    store = _rollout_store(kind, HH_LENGTHS)
    create = store.create_loader

    def pinned(*a, **kw):
        kw.setdefault("query_length", 896)
        kw.setdefault("response_length", 128)
        return create(*a, **kw)

    store.create_loader = pinned
    widths = sorted(b.query_tensors.shape[1] for b in _ladder_loader(store))
    assert widths == [256, 256, 512, 896]
    # a caller that names no length still gets the pin, and the uniform partition
    assert {b.query_tensors.shape[1] for b in store.create_loader(8, shuffle=True)} == {896}


@pytest.mark.parametrize("kind", ["ppo", "grpo"])
def test_a_row_over_the_budget_is_not_cut(kind):
    store = _rollout_store(kind, [64] * 7 + [1000])
    (batch,) = list(_ladder_loader(store))
    assert batch.query_tensors.shape == (8, 1000)
    assert int(np.asarray(batch.query_mask).sum(axis=1).max()) == 1000
