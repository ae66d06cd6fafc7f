"""Decode attention over static cache extents (CPU, float32, toy presets).

``ops/sampling.py::kv_extents`` gives ``generate()``'s decode steps a short
static list of cache extents; ``models/transformer.py::extent_attention``
attends over the first that holds the slot a step writes. Here:

- the extents function on the benchmark's shapes and on the edges;
- decode logits with the extents against without, at every step of a loop
  that crosses every boundary, and equal sampled tokens under one seed;
- one extent or ``None`` is the program without extents, jaxpr for jaxpr;
- a vector ``cache_index``, a span of tokens and a paged cache read the
  whole width whatever they are handed;
- ``rollout/kv_read_frac`` by host arithmetic and on a toy job's record.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import trlx_tpu.trlx as trlx
from trlx_tpu.data.default_configs import default_grpo_config, default_ppo_config
from trlx_tpu.models.builder import config_from_spec
from trlx_tpu.models.transformer import CausalTransformer, make_kv_cache
from trlx_tpu.ops import sampling
from trlx_tpu.ops.paged_kv import attach_block_table
from trlx_tpu.ops.sampling import GenerationConfig, generate, kv_extents, kv_slots_read

FAMILIES = ["mistral", "gptj", "olmoe", "falconh1"]
P, N = 6, 10  # with a bucket of 4: extents 8, 12, 16
# The Mistral toy's window of 8 is shorter than these rows of 16, and a window
# layer's cache is then a ring of 8 slots with one extent, its own
# (tests/test_smallthinker.py and test_mistral_ring_* below hold those). Here
# the window holds the row, so that the extents are what is tested.
WINDOW_OVER_THE_ROW = {"mistral": dict(sliding_window=64)}


@pytest.fixture
def bucket4(monkeypatch):
    monkeypatch.setattr(sampling, "KV_BUCKET", 4)


def _model(family, **kw):
    cfg = config_from_spec(f"builtin:{family}-test", dtype=jnp.float32,
                           param_dtype=jnp.float32, attention_impl="xla",
                           **{**WINDOW_OVER_THE_ROW.get(family, {}), **kw})
    model = CausalTransformer(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, P), 3, 50)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    return cfg, model, params, ids


# ---------------------------------------------------------------------------
# the extents
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape, want", [
    ((128, 512), (192, 256, 320, 384, 448, 512, 576, 640)),  # the three decode cells
    ((896, 128), (1024,)),               # the two hh cells: (960, 1024) would spare 3% of the reads
    ((5, 3), (8,)),                      # a toy job
    ((130, 100), (192, 230)),            # N under two buckets, one boundary crossed
    ((100, 60), (160,)),                 # N under one bucket: 9% spared is not worth a conditional
    ((100, 200), (128, 192, 256, 300)),  # S not a multiple of the bucket
    ((256, 256), (320, 384, 448, 512)),  # a prompt that ends on a boundary
    ((128, 1024), (256, 384, 512, 640, 768, 896, 1024, 1152)),  # the bucket doubles to stay at eight
    ((128, 2048), (512, 1024, 1536, 2048, 2176)),
])
def test_kv_extents(shape, want):
    got = kv_extents(*shape)
    assert got == want
    assert got[-1] == sum(shape) and got[0] > shape[0] and list(got) == sorted(set(got))
    assert len(got) <= sampling.MAX_KV_EXTENTS
    if len(got) > 1:
        spared = 1 - kv_slots_read(got, shape[0], shape[1]) / (shape[1] * sum(shape))
        assert spared >= sampling.MIN_KV_SAVING


# ---------------------------------------------------------------------------
# the same logits, the same tokens
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scan_layers", [False, True], ids=["layers", "scan"])
@pytest.mark.parametrize("family", FAMILIES)
def test_decode_logits_with_extents_match_without(family, scan_layers, bucket4):
    """Every step of a loop from slot 6 to slot 15 under extents (8, 12, 16):
    the steps either side of both boundaries are among them."""
    cfg, model, params, ids = _model(family, scan_layers=scan_layers)
    extents = kv_extents(P, N)
    assert extents == (8, 12, 16)
    mask = jnp.concatenate([jnp.ones((2, P), jnp.int32), jnp.zeros((2, N), jnp.int32)], 1)
    cache = model.apply({"params": params}, ids, attention_mask=mask, cache=make_kv_cache(cfg, 2, P + N),
                        cache_index=jnp.asarray(0, jnp.int32))["cache"]

    @jax.jit
    def step(cache, mask, token, slot):
        kw = dict(attention_mask=mask, cache=cache, cache_index=slot)
        whole = model.apply({"params": params}, token, **kw)
        short = model.apply({"params": params}, token, kv_extents=extents, **kw)
        return whole["logits"], short["logits"], whole["cache"], short["cache"]

    text = str(jax.make_jaxpr(lambda c, m: model.apply(
        {"params": params}, ids[:, :1], attention_mask=m, cache=c,
        cache_index=jnp.asarray(P, jnp.int32), kv_extents=extents))(cache, mask))
    assert "cond[" in text
    for i in range(N):
        mask = mask.at[:, P + i].set(1)
        token = jnp.full((2, 1), 7 + i, jnp.int32)
        whole, short, cache, short_cache = step(cache, mask, token, jnp.asarray(P + i, jnp.int32))
        np.testing.assert_allclose(np.asarray(short), np.asarray(whole), rtol=1e-5, atol=1e-5,
                                   err_msg=f"step {i}, slot {P + i}")
        # the cache is written and carried whole either way
        for a, b in zip(jax.tree_util.tree_leaves(cache), jax.tree_util.tree_leaves(short_cache)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("family", FAMILIES)
def test_generate_samples_the_same_tokens(family, monkeypatch):
    cfg, model, params, ids = _model(family)
    config = GenerationConfig(max_new_tokens=N, do_sample=True, temperature=1.0, top_k=0, top_p=1.0,
                              eos_token_id=None, pad_token_id=0)

    def run(bucket):
        monkeypatch.setattr(sampling, "KV_BUCKET", bucket)
        seen = []

        def apply_fn(p, tokens, **kw):
            seen.append(kw.get("kv_extents"))
            return model.apply({"params": p}, tokens, **kw)

        out = jax.jit(lambda p, i, m, r: generate(
            apply_fn, p, lambda B, S: make_kv_cache(cfg, B, S), i, m, r, config))(
            params, ids, jnp.ones_like(ids), jax.random.PRNGKey(7))
        return out, seen

    whole, seen_whole = run(1024)
    short, seen_short = run(4)
    assert seen_whole == [None, None]            # prefill, then the step: no key at all
    assert seen_short == [None, (8, 12, 16)]     # the step alone is handed the extents
    np.testing.assert_array_equal(np.asarray(short.response_tokens), np.asarray(whole.response_tokens))
    np.testing.assert_allclose(np.asarray(short.response_logprobs), np.asarray(whole.response_logprobs),
                               rtol=1e-5, atol=1e-5)


def test_pipelined_blocks_take_the_branch_too(monkeypatch):
    """Under a ``pipe`` mesh axis the blocks run through the GPipe schedule
    (``parallel/pipeline.py``), each a ``jax.checkpoint``-able closure: the
    extents reach ``Attention`` there as well, and greedy decoding agrees
    with the unpipelined run that reads the whole cache."""
    from trlx_tpu.data.configs import ModelConfig, ParallelConfig
    from trlx_tpu.models.builder import build_causal_lm
    from trlx_tpu.parallel.mesh import make_mesh, set_global_mesh
    from trlx_tpu.parallel.sharding import shard_batch, shard_params

    module, params, tcfg = build_causal_lm(
        ModelConfig(model_path="builtin:gpt2-test",
                    model_extra_kwargs=dict(scan_layers=True, num_layers=4)), head="value")
    ids = np.random.RandomState(1).randint(1, 259, (8, 10)).astype(np.int32)
    mask = np.ones((8, 10), np.int32)
    mask[:3, :4] = 0
    config = GenerationConfig(max_new_tokens=10, do_sample=False, eos_token_id=None)  # extents 12, 16, 20

    def run(p, ids, mask):
        return generate(lambda p, tokens, **kw: module.apply({"params": p}, tokens, **kw), p,
                        lambda B, S: make_kv_cache(tcfg, B, S), ids, mask, jax.random.PRNGKey(1), config)

    try:
        set_global_mesh(None)
        monkeypatch.setattr(sampling, "KV_BUCKET", 1024)  # the reference reads every slot
        whole = jax.jit(run)(params, jnp.asarray(ids), jnp.asarray(mask))
        monkeypatch.setattr(sampling, "KV_BUCKET", 4)
        mesh = make_mesh(ParallelConfig(data=1, pipe=2, fsdp=2, model=2))
        set_global_mesh(mesh)
        p = shard_params(params, mesh)
        b = shard_batch({"ids": ids, "mask": mask}, mesh)
        assert "cond[" in str(jax.make_jaxpr(run)(p, b["ids"], b["mask"]))
        short = jax.jit(run)(p, b["ids"], b["mask"])
    finally:
        set_global_mesh(None)
    same = np.asarray(whole.response_tokens) == np.asarray(short.response_tokens)
    assert same.mean() > 0.9  # bf16 reduction order may flip the odd argmax
    np.testing.assert_allclose(np.asarray(whole.response_logprobs)[same],
                               np.asarray(short.response_logprobs)[same], atol=3e-2)


# ---------------------------------------------------------------------------
# where nothing may change
# ---------------------------------------------------------------------------


def _step_jaxpr(family, kv_extents=None, tokens=1, cache_index=None, paged=False, **kw):
    cfg = config_from_spec(f"builtin:{family}-test", attention_impl="xla",
                           **{**WINDOW_OVER_THE_ROW.get(family, {}), **kw})
    model = CausalTransformer(cfg)
    ids = jnp.zeros((2, 12), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids)["params"])
    if paged:
        table = jnp.zeros((2, 4), jnp.int32)
        cache = jax.eval_shape(lambda: attach_block_table(make_kv_cache(cfg, 9, 4), table))
    else:
        cache = jax.eval_shape(lambda: make_kv_cache(cfg, 2, 16))
    slot = jnp.asarray(12, jnp.int32) if cache_index is None else cache_index
    text = str(jax.make_jaxpr(lambda p, c: model.apply(
        {"params": p}, ids[:, :tokens], attention_mask=jnp.ones((2, 16), jnp.int32), cache=c,
        cache_index=slot, kv_extents=kv_extents))(params, cache))
    return re.sub(r"0x[0-9a-f]+", "0x", text)


@pytest.mark.parametrize("family", FAMILIES)
def test_one_extent_traces_to_the_program_without(family):
    """``None`` is what every caller but the sampler's step passes, and what
    ``tests/test_falconh1.py`` holds to the hashes recorded before that
    family; a single extent must be that program too."""
    without = _step_jaxpr(family)
    assert "cond[" not in without
    assert _step_jaxpr(family, kv_extents=(16,)) == without
    assert "cond[" in _step_jaxpr(family, kv_extents=(8, 16))


@pytest.mark.parametrize("case", ["vector_cache_index", "token_span", "paged_cache", "remat_block"])
def test_other_callers_read_the_whole_width(case):
    """Speculative verify and slot refill (a ``[B]`` cache index), prefill
    (``T > 1``) and the paged Engine never take the conditional, whatever
    extents they are handed; a rematerialised block does, with static ints."""
    kw = {
        "vector_cache_index": dict(cache_index=jnp.asarray([12, 11], jnp.int32)),
        "token_span": dict(tokens=3),
        "paged_cache": dict(paged=True),
        "remat_block": dict(remat="full"),
    }[case]
    without = _step_jaxpr("mistral", **kw)
    with_extents = _step_jaxpr("mistral", kv_extents=(8, 16), **kw)
    if case == "remat_block":
        assert "cond[" not in without and "cond[" in with_extents
    else:  # (the paged kernel has conditionals of its own)
        assert with_extents == without


# ---------------------------------------------------------------------------
# the counter
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape, steps, want", [
    ((128, 512), 512, 0.65),   # 64 steps each at 192, 256, ..., 640 of 640
    ((896, 128), 128, 1.0),    # one extent
    ((128, 512), 200, (64 * (192 + 256 + 320) + 8 * 384) / (200 * 640)),  # an early exit stays low in the cache
    ((128, 512), 1, 192 / 640),
])
def test_kv_read_frac_arithmetic(shape, steps, want):
    extents = kv_extents(*shape)
    assert kv_slots_read(extents, shape[0], steps) / (steps * sum(shape)) == pytest.approx(want, abs=1e-12)


def test_kv_read_frac_at_the_bucket_the_issue_wrote_down(monkeypatch):
    """ISSUE 32 priced a 128-slot bucket: 128 steps each at 256, 384, 512, 640."""
    monkeypatch.setattr(sampling, "KV_BUCKET", 128)
    extents = kv_extents(128, 512)
    assert extents == (256, 384, 512, 640)
    assert kv_slots_read(extents, 128, 512) / (512 * 640) == pytest.approx(0.70, abs=1e-12)


class Recorder:
    def __init__(self):
        self.records = []

    def log(self, stats, step=None):
        self.records.append(dict(stats))

    def finish(self):
        pass


@pytest.mark.parametrize("method", ["ppo", "grpo"])
def test_collection_record_carries_kv_read_frac(method, tmp_path, bucket4):
    """A toy job whose decode loop crosses extents: the record's share is the
    host arithmetic over each chunk's own decode steps (an early exit ends a
    chunk's sum where its longest row ended)."""
    default = default_grpo_config if method == "grpo" else default_ppo_config
    config = default().evolve(
        train=dict(seq_length=24, batch_size=8, total_steps=1, eval_interval=10, checkpoint_interval=10,
                   epochs=1, save_best=False, tracker=None, checkpoint_dir=str(tmp_path / "ckpts"),
                   logging_dir=str(tmp_path / "logs"), rollout_pipeline_depth=0),
        model=dict(model_path="builtin:gpt2-test", num_layers_unfrozen=1),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        method=dict(num_rollouts=16, chunk_size=8, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=12, top_k=0, top_p=1.0, do_sample=True),
                    **(dict(group_size=4) if method == "grpo" else {})),
    )
    recorder, chunks = Recorder(), []

    def hook(trainer):
        trainer.tracker = recorder
        inner = trainer.generate

        def capturing(input_ids, *a, **kw):
            out = inner(input_ids, *a, **kw)
            if not kw.get("eval_mode", False):
                chunks.append((np.asarray(input_ids).shape[1], np.asarray(out.response_mask)))
            return out

        trainer.generate = capturing

    trlx.train(reward_fn=lambda samples, **kw: [float(len(s)) for s in samples],
               prompts=["ab", "cd", "ef", "gh", "ij", "kl", "mn", "op"], config=config,
               init_trainer_hook=hook)
    rec = next(r for r in recorder.records if "time/exp" in r)
    read = total = 0
    for width, mask in chunks:
        new, steps = mask.shape[1], int(mask.sum(axis=1).max())
        extents = kv_extents(width, new)
        assert len(extents) > 1
        read += kv_slots_read(extents, width, steps)
        total += steps * (width + new)
    assert total > 0
    assert rec["rollout/kv_read_frac"] == pytest.approx(read / total)
    assert rec["rollout/kv_read_frac"] < 1.0


# ---------------------------------------------------------------------------
# a uniform window shorter than the row: every layer's cache is a ring
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scan_layers", [False, True], ids=["layers", "scan"])
def test_mistral_ring_decode_matches_the_windowed_full_forward(scan_layers, bucket4):
    """The Mistral toy at its own window of 8 on rows of 16: ``make_kv_cache``
    gives every layer 8 slots, the prefill of 6 does not wrap, the ten steps
    do, and each step's logits are the windowed full forward's; the extents
    (8, 12, 16) cut to the ring leave one, so the step has no conditional."""
    cfg, model, params, ids = _model("mistral", scan_layers=scan_layers, sliding_window=8)
    cache = make_kv_cache(cfg, 2, P + N)
    assert {leaf.shape[-3] for leaf in jax.tree_util.tree_leaves(cache)} == {8}
    tokens = jnp.concatenate([ids, 7 + jnp.arange(N)[None].repeat(2, 0)], axis=1)
    want = model.apply({"params": params}, tokens, attention_mask=jnp.ones((2, P + N), jnp.int32))["logits"]
    mask = jnp.concatenate([jnp.ones((2, P), jnp.int32), jnp.zeros((2, N), jnp.int32)], 1)
    out = model.apply({"params": params}, ids, attention_mask=mask, cache=cache,
                      cache_index=jnp.asarray(0, jnp.int32))
    step = lambda c, m, t, s: model.apply({"params": params}, t, attention_mask=m, cache=c,
                                          cache_index=s, kv_extents=kv_extents(P, N))
    assert "cond[" not in str(jax.make_jaxpr(step)(out["cache"], mask, tokens[:, P:P + 1], jnp.asarray(P, jnp.int32)))
    for i in range(N):
        mask = mask.at[:, P + i].set(1)
        out = jax.jit(step)(out["cache"], mask, tokens[:, P + i : P + i + 1], jnp.asarray(P + i, jnp.int32))
        np.testing.assert_allclose(np.asarray(out["logits"][:, 0]), np.asarray(want[:, P + i]),
                                   rtol=2e-4, atol=2e-4, err_msg=f"slot {P + i}")
