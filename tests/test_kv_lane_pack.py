"""Lane-packed K and V (``ops/cache_layout.py::lane_heads``): at a head under
128 a dense ``k`` / ``v`` leaf holds ``P = 128 // D`` KV heads side by side in
its minor axis, ``[B, slots, KV / P, P * D]``, and ``Attention`` reads ``P``
off the leaf it is handed. The program over a cache made with
``lane_packed=False`` is the one the repository ran before the packing; every
case here holds the packed program to it on the CPU: bit for bit in bfloat16
(and wherever no attention output is compared), to float32's order of
summation in float32, where XLA:CPU's contraction over 128 lanes adds its 64
real products (and 64 exact zeros) in another order than the one over 64.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.models.transformer import CausalTransformer, config_from_spec, make_kv_cache, write_row_spans
from trlx_tpu.ops.cache_layout import KV, cache_bytes, cache_slots, describe, kv_lane_heads, lane_heads, lane_pack, lane_unpack
from trlx_tpu.ops.sampling import GenerationConfig

HEADS = {"gqa_32_8": (32, 8), "mha_8_8": (8, 8)}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
B, S, PROMPT = 2, 24, 9


def config(heads, dtype, **overrides):
    """One llama layer pair at a head of 64 (hidden 64: the projections give
    the heads their width), xla attention."""
    H, KVH = HEADS[heads]
    return config_from_spec("builtin:llama-test", **{**dict(num_heads=H, num_kv_heads=KVH, head_dim=64, attention_impl="xla",
                                                            dtype=DTYPES[dtype], param_dtype=jnp.float32), **overrides})


def setup(heads, dtype, **overrides):
    cfg = config(heads, dtype, **overrides)
    model = CausalTransformer(cfg)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(3, 250, size=(B, S)), jnp.int32)
    params = model.init(jax.random.PRNGKey(1), ids[:, :4])["params"]
    slot_mask = jnp.ones((B, S), jnp.int32).at[0, :3].set(0)  # row 0 behind three pads
    return cfg, model, params, ids, slot_mask


def caches(cfg, slots=S):
    return make_kv_cache(cfg, B, slots), make_kv_cache(cfg, B, slots, lane_packed=False)


def unpacked(cache, cfg):
    side = kv_lane_heads(cache, cfg.dims_per_head)
    return [{name: lane_unpack(leaf, side) if name in ("k", "v") else leaf for name, leaf in layer.items()} for layer in cache]


def assert_same(a, b):
    """Bit for bit in bfloat16; in float32 to the order of a sum of 64 products of numbers about 1."""
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_allclose(np.asarray(x.astype(jnp.float32)), np.asarray(y.astype(jnp.float32)), rtol=0,
                                   atol=0 if x.dtype == jnp.bfloat16 else 4e-6)


def prefilled(model, params, ids, slot_mask, cache):
    out = model.apply({"params": params}, ids[:, :PROMPT], attention_mask=slot_mask, cache=cache, cache_index=jnp.asarray(0, jnp.int32))
    return out["logits"], out["cache"]


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("head_dim, kv_heads, side", [
    (64, 8, 2), (64, 2, 2), (32, 8, 4), (64, 1, 1), (128, 8, 1), (256, 16, 1), (16, 2, 1), (96, 8, 1)])
def test_the_rule_is_a_function_of_the_head_and_the_kv_heads(head_dim, kv_heads, side):
    assert lane_heads(head_dim, kv_heads) == side
    cfg = config_from_spec("builtin:llama-test", num_heads=kv_heads, num_kv_heads=kv_heads, head_dim=head_dim)
    cache = jax.eval_shape(lambda: make_kv_cache(cfg, 3, 10))
    assert cache[0]["k"].shape == cache[0]["v"].shape == (3, 10, kv_heads // side, side * head_dim)
    assert kv_lane_heads(cache, head_dim) == side
    plain = jax.eval_shape(lambda: make_kv_cache(cfg, 3, 10, lane_packed=False))
    assert plain[0]["k"].shape == (3, 10, kv_heads, head_dim) and kv_lane_heads(plain, head_dim) == 1
    x = jnp.arange(3 * 10 * kv_heads * head_dim, dtype=jnp.float32).reshape(3, 10, kv_heads, head_dim)
    packed = lane_pack(x, side)
    assert (packed is x) == (side == 1) and packed.shape == cache[0]["k"].shape
    np.testing.assert_array_equal(np.asarray(lane_unpack(packed, side)), np.asarray(x))
    # head j's channels stand in lanes [(j % side) * D, (j % side + 1) * D) of row j // side
    np.testing.assert_array_equal(np.asarray(packed[1, 2, (kv_heads - 1) // side, ((kv_heads - 1) % side) * head_dim:][:head_dim]),
                                  np.asarray(x[1, 2, kv_heads - 1]))


# ---------------------------------------------------------------------------
# the decode step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("extents", [None, (8, 16, 24)], ids=["one_extent", "three_extents"])
@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("heads", list(HEADS))
def test_a_decode_step_over_a_packed_cache_equals_the_unpacked_programs(heads, dtype, extents):
    """Prefill nine slots, then six single-token steps (crossing two of the
    three extents): logits and cache of every step equal the unpacked
    program's (``assert_same``: to the bit in bfloat16), and the packed leaves
    are the unpacked ones' row-major reshape."""
    cfg, model, params, ids, slot_mask = setup(heads, dtype)
    packed, plain = caches(cfg)
    assert packed[0]["k"].shape == (B, S, cfg.kv_heads // 2, 128) and plain[0]["k"].shape == (B, S, cfg.kv_heads, 64)
    (logits_p, packed), (logits_u, plain) = (prefilled(model, params, ids, slot_mask, c) for c in (packed, plain))
    assert_same(logits_p, logits_u)

    @jax.jit
    def step(cache, token, at):
        out = model.apply({"params": params}, token, attention_mask=slot_mask, cache=cache, cache_index=at, kv_extents=extents)
        return out["logits"], out["cache"]

    for at in range(PROMPT, PROMPT + 9):
        token, index = ids[:, at : at + 1], jnp.asarray(at, jnp.int32)
        (logits_p, packed), (logits_u, plain) = step(packed, token, index), step(plain, token, index)
        assert_same(logits_p, logits_u)
        assert_same(unpacked(packed, cfg), plain)
    assert np.isfinite(np.asarray(logits_p, np.float32)).all() and float(jnp.abs(logits_p.astype(jnp.float32)).max()) > 0


def test_the_packed_step_has_no_unpacked_view_of_the_cache():
    """Inside a decode loop a ``[.., KV, D]`` view of the carried leaf is what
    turns it slot-minor on the chip: the step's jaxpr reshapes no array of the
    cache's size."""
    cfg, model, params, ids, slot_mask = setup("gqa_32_8", "bfloat16")
    cache = make_kv_cache(cfg, B, S)
    jaxpr = jax.make_jaxpr(lambda c: model.apply({"params": params}, ids[:, :1], attention_mask=slot_mask, cache=c,
                                                 cache_index=jnp.asarray(12, jnp.int32), kv_extents=(8, 16, 24))["logits"])(cache)
    text = str(jaxpr)
    assert f"bf16[{B},{S},4,128]" in text
    for extent in (8, 16, 24, S):
        assert f"bf16[{B},{extent},8,64]" not in text, extent


# ---------------------------------------------------------------------------
# the writers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("heads", list(HEADS))
def test_a_prefill_from_slot_0_leaves_the_unpacked_programs_cache(heads, impl):
    """The span at slot 0 (the flash call over the cache unpacks it; the
    einsum reads it as it lies): same logits, same rows in the leaf."""
    cfg, model, params, ids, slot_mask = setup(heads, "bfloat16", attention_impl=impl)
    packed, plain = caches(cfg)
    (logits_p, packed), (logits_u, plain) = (prefilled(model, params, ids, slot_mask, c) for c in (packed, plain))
    assert packed[0]["k"].shape[-1] == 128
    assert_same(unpacked(packed, cfg), plain)
    assert_same(logits_p, logits_u)
    assert float(jnp.abs(packed[0]["k"][:, PROMPT:]).max()) == 0.0 and float(jnp.abs(packed[1]["v"][1, :PROMPT]).min()) > 0.0


@pytest.mark.parametrize("span", [1, 3])
@pytest.mark.parametrize("heads", list(HEADS))
def test_row_spans_at_each_rows_own_slot_leave_the_unpacked_programs_cache(heads, span):
    """``write_row_spans`` at a ``[B]`` vector of slots (a speculative round's
    verify, the slot engine's dense segment): the model's span over the
    packed cache equals the unpacked program's, and so does the bare write."""
    cfg, model, params, ids, slot_mask = setup(heads, "bfloat16")
    packed, plain = (prefilled(model, params, ids, slot_mask, c)[1] for c in caches(cfg))
    at = jnp.asarray([PROMPT, PROMPT - 2], jnp.int32)
    outs = [model.apply({"params": params}, ids[:, PROMPT : PROMPT + span], attention_mask=slot_mask, cache=c, cache_index=at)
            for c in (packed, plain)]
    assert_same(outs[0]["logits"], outs[1]["logits"])
    assert_same(unpacked(outs[0]["cache"], cfg), outs[1]["cache"])
    rows = jax.random.normal(jax.random.PRNGKey(2), (B, span, cfg.kv_heads, 64), jnp.bfloat16)
    leaf = write_row_spans(packed[0]["k"], lane_pack(rows, 2), at)
    assert_same(lane_unpack(leaf, 2), write_row_spans(plain[0]["k"], rows, at))


@pytest.mark.parametrize("heads", list(HEADS))
def test_a_ring_of_fewer_slots_than_the_row_leaves_the_unpacked_programs_cache(heads):
    """A window layer's ring (C = 8 slots of a row of 24): the prefill of nine
    tokens wraps, then steps write slot t at t mod C."""
    cfg, model, params, ids, slot_mask = setup(heads, "bfloat16", sliding_window=8)
    packed, plain = caches(cfg)
    assert cache_slots(packed[0]) == 8 and packed[0]["k"].shape == (B, 8, cfg.kv_heads // 2, 128)
    (logits_p, packed), (logits_u, plain) = (prefilled(model, params, ids, slot_mask, c) for c in (packed, plain))
    assert_same(logits_p, logits_u)
    for at in range(PROMPT, PROMPT + 10):
        outs = [model.apply({"params": params}, ids[:, at : at + 1], attention_mask=slot_mask, cache=c,
                            cache_index=jnp.asarray(at, jnp.int32), kv_extents=(16, 24)) for c in (packed, plain)]
        assert_same(outs[0]["logits"], outs[1]["logits"])
        packed, plain = outs[0]["cache"], outs[1]["cache"]
        assert_same(unpacked(packed, cfg), plain)


def test_a_block_pool_is_not_packed_and_says_so():
    """The paged kernels read ``[NB, bs, KV, D]``: a packed pool is refused in
    words, an unpacked one runs as it did."""
    from trlx_tpu.ops.paged_kv import attach_block_table

    cfg, model, params, ids, slot_mask = setup("mha_8_8", "float32")
    table = jnp.arange(B * 3, dtype=jnp.int32).reshape(B, 3)  # rows of 24 slots in blocks of 8
    step = lambda pool: model.apply({"params": params}, ids[:, :1], attention_mask=slot_mask, cache=attach_block_table(pool, table),
                                    cache_index=jnp.zeros((B,), jnp.int32))
    with pytest.raises(ValueError, match="lane_packed=False"):
        step(make_kv_cache(cfg, 8, 8))
    out = step(make_kv_cache(cfg, 8, 8, lane_packed=False))
    assert out["cache"][0]["k"].shape == (8, 8, 8, 64) and np.isfinite(np.asarray(out["logits"])).all()


# ---------------------------------------------------------------------------
# the description and the counter
# ---------------------------------------------------------------------------


def test_describe_reads_the_same_slots_and_bytes_of_a_packed_and_an_unpacked_cache():
    cfg = config("gqa_32_8", "bfloat16")
    packed, plain = (jax.eval_shape(lambda p=p: make_kv_cache(cfg, 4, 40, lane_packed=p)) for p in (True, False))
    assert [tuple(leaf) for leaf in describe(packed)] == [tuple(leaf) for leaf in describe(plain)]
    assert describe(packed)[0] == ("k", KV, 40, 4 * 40 * 8 * 64 * 2)
    assert cache_bytes(packed, 40) == cache_bytes(plain, 40) and cache_slots(packed[0]) == 40
    assert kv_lane_heads(packed, 64) == 2 and kv_lane_heads(plain, 64) == 1


@pytest.mark.parametrize("model_kwargs, side", [
    (dict(model_path="builtin:llama-test", model_extra_kwargs=dict(num_heads=4, num_kv_heads=2, head_dim=64)), 2.0),
    (dict(model_path="builtin:mistral-test"), 1.0)], ids=["head_64_kv_2", "mistral_test"])
def test_the_collection_record_says_how_many_heads_a_row_holds(tmp_path, model_kwargs, side):
    """``rollout/kv_lane_heads`` beside ``rollout/kv_cache_bytes`` on a toy
    GRPO job's collection record: 2 at a head of 64 with two KV heads (the
    job generates, scores and learns over the packed cache), 1 where nothing
    packs; the bytes are the unpacked leaves'."""
    import trlx_tpu.trlx as trlx
    from trlx_tpu.data.default_configs import default_grpo_config

    config = default_grpo_config().evolve(
        train=dict(seq_length=32, batch_size=4, total_steps=1, eval_interval=10, checkpoint_interval=10, epochs=1, save_best=False,
                   tracker=None, checkpoint_dir=str(tmp_path / "ckpts"), logging_dir=str(tmp_path / "logs")),
        model=dict(num_layers_unfrozen=-1, **model_kwargs),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        method=dict(num_rollouts=8, chunk_size=8, group_size=4, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=8, min_new_tokens=8, top_k=0, top_p=1.0, do_sample=True)),
    )
    records = []

    def hook(trainer):
        trainer.tracker = types.SimpleNamespace(log=lambda stats, step=None: records.append(dict(stats)), finish=lambda: None)

    trainer = trlx.train(reward_fn=lambda samples, **kw: [float(len(s)) for s in samples], prompts=["abcdefghijkl", "mnopqrstuvwx"],
                         config=config, init_trainer_hook=hook)
    collection = next(r for r in records if "time/exp" in r)
    assert collection["rollout/kv_lane_heads"] == side
    cfg = trainer.tcfg
    assert lane_heads(cfg.dims_per_head, cfg.kv_heads) == side
    width = cfg.dtype.dtype.itemsize
    per_slot = 2 * cfg.num_layers * 8 * cfg.kv_heads * cfg.dims_per_head * width
    assert collection["rollout/kv_cache_bytes"] % per_slot == 0 or cfg.sliding_window
    assert trainer.last_cache_stats["rollout/kv_lane_heads"] == side
    trainer._note_dense_kv_gauge((3, 21), GenerationConfig(max_new_tokens=19))
    assert trainer.last_cache_stats["rollout/kv_lane_heads"] == side


# ---------------------------------------------------------------------------
# the other rollout paths: a speculative round and the slot-refill engine's dense segment
# ---------------------------------------------------------------------------


def _value_lm(seed=0):
    from trlx_tpu.data.configs import ModelConfig
    from trlx_tpu.models.builder import build_causal_lm

    kw = dict(num_heads=4, num_kv_heads=2, head_dim=64, dtype=jnp.float32, param_dtype=jnp.float32)
    module, params, tcfg = build_causal_lm(ModelConfig("builtin:llama-test", model_extra_kwargs=kw), head="value", seed=seed)
    assert lane_heads(tcfg.dims_per_head, tcfg.kv_heads) == 2
    return (lambda p, i, **k: module.apply({"params": p}, i, **k)), params, tcfg


def _plain_rollout(lm, ids, mask, cfg, rng, **kw):
    """The plain sampler over an UNPACKED cache: the program before the packing."""
    from trlx_tpu.ops.sampling import generate

    apply_fn, params, tcfg = lm
    return generate(apply_fn, params, lambda b, s: make_kv_cache(tcfg, b, s, lane_packed=False), ids, mask, rng, cfg, **kw)


def _assert_rollouts_agree(out, ref):
    np.testing.assert_array_equal(np.asarray(out.response_tokens), np.asarray(ref.response_tokens))
    np.testing.assert_array_equal(np.asarray(out.response_mask), np.asarray(ref.response_mask))
    np.testing.assert_allclose(np.asarray(out.response_logprobs), np.asarray(ref.response_logprobs), atol=1e-5)
    np.testing.assert_allclose(np.asarray(out.response_values), np.asarray(ref.response_values), atol=1e-5)


@pytest.mark.parametrize("gamma", [1, 3])
def test_a_speculative_round_over_packed_caches_is_the_plain_samplers_greedy_rollout(gamma):
    """Target and draft both hold packed caches: the draft's steps and the
    target's verify span write at each row's own slot (``write_row_spans``)
    and read the leaf as it lies; greedy output equals the plain sampler's
    over an unpacked cache."""
    from trlx_tpu.ops.speculative import generate_speculative

    target, draft = _value_lm(0), _value_lm(1)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(3, 250, size=(3, 8)), jnp.int32)
    mask = jnp.ones((3, 8), jnp.int32).at[0, :3].set(0).at[2, :5].set(0)
    cfg = GenerationConfig(max_new_tokens=10, do_sample=False, eos_token_id=None, pad_token_id=258)
    packed = lambda lm: (lambda b, s: make_kv_cache(lm[2], b, s))
    assert kv_lane_heads(jax.eval_shape(lambda: packed(target)(3, 20)), 64) == 2
    out = jax.jit(lambda i, m: generate_speculative(target[0], target[1], draft[0], draft[1], packed(target), packed(draft),
                                                    i, m, jax.random.PRNGKey(0), cfg, gamma=gamma))(ids, mask)
    _assert_rollouts_agree(out, _plain_rollout(target, ids, mask, cfg, jax.random.PRNGKey(0)))


def test_the_slot_refill_engine_over_a_packed_cache_is_the_plain_samplers_rollout():
    """Six prompts through three slots, refilled as rows end: the dense
    segment's per-row writes and the refill's row copies move packed rows;
    every completed row equals the plain sampler's over an unpacked cache."""
    from trlx_tpu.ops.sampling import per_row_keys
    from trlx_tpu.ops.slot_refill import make_slot_refill_fns
    from trlx_tpu.pipeline.continuous_batching import ContinuousBatchingEngine

    lm = _value_lm(0)
    apply_fn, params, tcfg = lm
    slots, n, width = 3, 6, 8
    rng = np.random.RandomState(1)
    prompts = rng.randint(3, 200, (n, width)).astype(np.int32)
    masks = np.ones_like(prompts)
    for i in range(n):
        masks[i, : i % 4] = 0
    prompts[masks == 0] = 258
    cfg = GenerationConfig(max_new_tokens=7, do_sample=False, eos_token_id=3, pad_token_id=258, per_row_rng=True)
    boost = lambda step_out, logits: logits.at[..., 3].add(4.0)  # rows end at unlike lengths: slots refill
    call = jax.random.PRNGKey(5)
    fns = make_slot_refill_fns(apply_fn, lambda b, s: make_kv_cache(tcfg, b, s), slots, width, cfg, adjust_logits=boost,
                               segment_len=3, params_example=params)
    engine = ContinuousBatchingEngine(fns, params, 258)
    keys = np.concatenate([np.asarray(per_row_keys(call, slots))] * (n // slots))
    engine.enqueue_prompts(prompts, masks, keys)
    got = {}
    while engine.busy:
        for done in engine.step():
            got[done.index] = done
    assert sorted(got) == list(range(n))
    for start in range(0, n, slots):
        ref = _plain_rollout(lm, jnp.asarray(prompts[start : start + slots]), jnp.asarray(masks[start : start + slots]), cfg, call,
                             adjust_logits=boost)
        for i in range(slots):
            row = got[start + i]
            np.testing.assert_array_equal(row.tokens, np.asarray(ref.response_tokens[i]))
            np.testing.assert_array_equal(row.mask, np.asarray(ref.response_mask[i]))
            np.testing.assert_allclose(row.logprobs, np.asarray(ref.response_logprobs[i]), atol=1e-5)
    assert 0 < min(int(got[i].mask.sum()) for i in got) < 7  # some row did end early
