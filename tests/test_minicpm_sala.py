"""MiniCPM-SALA (``model_type`` ``minicpm_sala``): lightning linear-attention
layers whose state is the layer's whole cache, beside GQA layers that attend
to chosen blocks of keys, against the plain reference
(``chipbench/reference/minicpm_sala.py``) at toy widths on the CPU.

``builtin:minicpm-sala-test``: sparse, lightning, lightning, sparse; blocks
of 8 keys, kernels of 4 every 2, 5 blocks a query, dense under 32 slots.
"""

import hashlib
import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import trlx_tpu.models.transformer as tf
from chipbench.reference import minicpm_sala as ref
from trlx_tpu.models.transformer import (
    CausalTransformer,
    block_selected_pairs,
    block_selected_steps,
    config_from_spec,
    make_kv_cache,
    select_blocks,
)
from trlx_tpu.ops.sampling import GenerationConfig

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
CFG = config_from_spec("builtin:minicpm-sala-test", **F32)
MODEL = CausalTransformer(CFG)
DIMS = dict(
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, lightning_nh=4, lightning_head_dim=16,
    rms_norm_eps=1e-6, rope_theta=10000, scale_depth=0.7 * np.sqrt(32), num_hidden_layers=4,
    hidden_size=64, dim_model_base=32, scale_emb=2.0,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"],
    sparse_config=dict(kernel_size=4, kernel_stride=2, block_size=8, topk=5, init_blocks=1, window_size=12, dense_len=32),
)


def seeded(params, seed=0):
    """Weights at which every mechanism shows: matrices of unit gain, norm
    scales scattered about 1, the per-head q and k scales about 2."""

    def leaf(path, x):
        name = jax.tree_util.keystr(path)
        rs = np.random.RandomState(int(hashlib.sha256(f"{seed}{name}".encode()).hexdigest()[:8], 16))
        if name.endswith("['scale']"):
            return jnp.asarray((2.0 if "q_norm" in name or "k_norm" in name else 1.0) + 0.3 * rs.randn(*x.shape), x.dtype)
        if name.endswith("['kernel']") or "lora_" in name:
            return jnp.asarray(rs.randn(*x.shape) / np.sqrt(x.shape[0]), x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(leaf, params)


def init(model=MODEL, seed=0):
    return seeded(model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"], seed)


PARAMS = init()


def batch(T, pads, seed=1):
    rs = np.random.RandomState(seed)
    ids = jnp.asarray(rs.randint(0, 259, (len(pads), T)))
    mask = jnp.asarray(np.arange(T)[None, :] >= np.asarray(pads)[:, None], jnp.int32)
    return ids, mask


def rel(a, b, mask):
    m = np.asarray(mask)[..., None]
    return float(np.sqrt(np.sum(((np.asarray(a) - np.asarray(b)) * m) ** 2) / np.sum((np.asarray(b) * m) ** 2)))


# ---------------------------------------------------------------------------
# the whole forward against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("T", [24, 56], ids=["below_dense_len", "above_dense_len"])
def test_logits_match_the_reference_on_left_padded_rows_of_unlike_length(T, impl):
    ids, mask = batch(T, [0, 5, 11])
    model = CausalTransformer(config_from_spec("builtin:minicpm-sala-test", attention_impl=impl, **F32))
    got = model.apply({"params": PARAMS}, ids, attention_mask=mask)["logits"]
    want = ref.logits(PARAMS, DIMS, ids, mask, (0, T))
    assert rel(got, want, mask) < 2e-5


def test_a_rows_logits_do_not_depend_on_the_padding_in_front_of_it():
    """Kernels and blocks are laid from the row's first real token: the same
    41 tokens behind 3, 7 and 15 pads (a whole block, a part of one) in rows
    of one width give the same logits, in the program and in the reference."""
    T, n = 56, 41
    rs = np.random.RandomState(3)
    tokens = rs.randint(0, 259, n)
    pads = [3, 7, 15]
    ids = jnp.asarray(np.stack([np.concatenate([np.zeros(p, int), tokens, np.zeros(T - n - p, int)]) for p in pads]))
    mask = jnp.asarray(np.stack([np.concatenate([np.zeros(p, int), np.ones(n, int), np.zeros(T - n - p, int)]) for p in pads]))
    for logits in (MODEL.apply({"params": PARAMS}, ids, attention_mask=mask)["logits"], ref.logits(PARAMS, DIMS, ids, mask, (0, T))):
        rows = [np.asarray(logits[i, p : p + n]) for i, p in enumerate(pads)]
        np.testing.assert_allclose(rows[1], rows[0], atol=2e-5)
        np.testing.assert_allclose(rows[2], rows[0], atol=2e-5)


@pytest.mark.parametrize("P", [37, 38], ids=["kernel_completes_at_the_first_step", "kernel_completes_at_the_second"])
def test_prefill_then_decode_through_state_and_kbar_matches_the_full_forward(P):
    """The sampler's path: a prefill from slot 0 that leaves each lightning
    layer's state and each sparse layer's K, V and complete kernels, then one
    token a step; rows behind unlike padding complete their kernels at unlike
    steps. Against the reference's full forward."""
    T = 56
    ids, mask = batch(T, [0, 5])
    want = ref.logits(PARAMS, DIMS, ids, mask, (0, T))
    cache = make_kv_cache(CFG, 2, T)
    assert [sorted(layer) for layer in cache] == [["k", "kbar", "v"], ["state"], ["state"], ["k", "kbar", "v"]]
    slots = jnp.concatenate([mask[:, :P], jnp.zeros((2, T - P), jnp.int32)], axis=1)
    out = MODEL.apply({"params": PARAMS}, ids[:, :P], attention_mask=slots, cache=cache, cache_index=0)
    step = jax.jit(lambda c, tok, m, t: MODEL.apply(
        {"params": PARAMS}, tok, attention_mask=m, cache=c, cache_index=t, kv_extents=(48, 56)))
    got = [out["logits"][:, -1]]
    cache = out["cache"]
    for t in range(P, T - 1):
        slots = slots.at[:, t].set(1)
        out = step(cache, ids[:, t : t + 1], slots, jnp.asarray(t, jnp.int32))
        cache = out["cache"]
        got.append(out["logits"][:, 0])
    got = jnp.stack(got, axis=1)
    assert rel(got, want[:, P - 1 : T - 1], mask[:, P - 1 : T - 1]) < 2e-5
    # the compressed keys the decode loop left are the full pass's
    k_full = MODEL.apply({"params": PARAMS}, ids[:, : T - 1], attention_mask=mask[:, : T - 1],
                         cache=make_kv_cache(CFG, 2, T), cache_index=0)["cache"][0]["kbar"]
    for row, pad in enumerate([0, 5]):
        complete = (T - 1 - pad - 4) // 2 + 1
        np.testing.assert_allclose(cache[0]["kbar"][row, :, :complete], k_full[row, :, :complete], atol=1e-5)


@pytest.mark.parametrize("fault", ref.FAULTS + ref.PRECISION_CONTROLS)
def test_every_planted_fault_of_the_reference_is_caught(fault):
    """Each other reading of what the catalog row does not settle, planted in
    the reference, moves the float32 logits well past the agreement above
    (2e-5). ``bf16_state`` is the mildest, a control for precision."""
    T = 56
    ids, mask = batch(T, [0, 5, 11])
    got = MODEL.apply({"params": PARAMS}, ids, attention_mask=mask)["logits"]
    moved = rel(got, ref.logits(PARAMS, DIMS, ids, mask, (0, T), fault=fault), mask)
    assert moved > (5e-4 if fault == "bf16_state" else 5e-3), (fault, moved)


@pytest.mark.parametrize("fault", [None, "bf16_state"], ids=["float32_state", "bf16_state_control"])
def test_the_samplers_cached_states_are_the_references_recurrence(fault):
    """``chipbench/state_check.py``'s reading, at toy widths in float32: the
    sampler's prefill and one-token steps on left-padded rows leave every
    lightning layer's ``state`` at the reference's ``S`` (``layer_states`` of the
    inputs that layer saw) to rounding, after the prefill and after the steps; the control (``S`` rounded
    to bfloat16 after every token) stands a thousand times further off, and the
    check's verdict holds a reading to its limits."""
    from chipbench import state_check

    P, N = 40, 24
    ids, mask = batch(P + N, [0, 5, 11, 2])
    gen = types.SimpleNamespace(sequences=ids, prompt_mask=mask[:, :P], response_mask=jnp.ones((4, N), jnp.int32),
                                response_tokens=ids[:, P:])
    trainer = types.SimpleNamespace(state=types.SimpleNamespace(params=PARAMS), module=MODEL, tcfg=CFG)
    got = state_check.state_readings(trainer, {"family": "minicpm_sala", "published": DIMS}, gen, fault=fault)
    assert (got["state_layers"], got["state_rows"], got["state_steps"]) == ([1, 2], 4, N)
    readings = {k: got[k] for k in ("state_rel_l2_prefill", "state_rel_l2_decode")}
    limits = dict.fromkeys(readings, 2e-5)
    if fault is None:
        assert state_check.verdict(readings, limits), readings
    else:
        assert min(readings.values()) > 2e-3 and not state_check.verdict(readings, limits), readings
    assert not state_check.verdict(readings, {}) and not state_check.verdict({}, limits)  # no limit, no reading: not correct
    assert set(state_check.load_limits("minicpm-sala-9b-l8")) == set(readings)


def test_one_ppo_step_under_lora_has_the_references_loss_and_gradients():
    """A clipped PPO objective on the response's logprobs with adapters (r 4,
    B not zero) on q, k, v, o of both kinds of layer: the loss and the
    gradients with respect to every adapter, through both scans and both
    selections, against the reference differentiated by jax."""
    cfg = config_from_spec("builtin:minicpm-sala-test", lora_r=4, lora_alpha=8.0,
                           lora_targets=("q_proj", "k_proj", "v_proj", "o_proj"), **F32)
    model = CausalTransformer(cfg)
    params = init(model, seed=2)
    T, P = 48, 36
    ids, mask = batch(T, [0, 7])
    rs = np.random.RandomState(5)
    old, adv = jnp.asarray(-5.5 + 0.3 * rs.randn(2, T - P)), jnp.asarray(rs.randn(2, T - P))

    def objective(logits):
        logp = jnp.take_along_axis(jax.nn.log_softmax(logits[:, P - 1 : T - 1]), ids[:, P:, None], axis=-1)[..., 0]
        ratio = jnp.exp(logp - old)
        return jnp.mean(jnp.maximum(-adv * ratio, -adv * jnp.clip(ratio, 0.8, 1.2)))

    adapters = lambda tree: {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_leaves_with_path(tree)
                             if "lora_" in jax.tree_util.keystr(p)}
    loss, grads = jax.value_and_grad(lambda p: objective(model.apply({"params": p}, ids, attention_mask=mask)["logits"]))(params)
    dims = dict(DIMS, lora_alpha=8.0)
    want_loss, want = jax.value_and_grad(lambda p: objective(ref.logits(p, dims, ids, mask, (0, T))))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * abs(float(want_loss))
    got, want = adapters(grads), adapters(want)
    assert len(got) == 4 * 4 * 2
    for name in got:
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0 and float(jnp.abs(got[name] - want[name]).max()) < 2e-4 * scale + 1e-8, name


# ---------------------------------------------------------------------------
# the scan, the selection, the kernels, the pieces
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [8, 32])
def test_the_chunked_scan_is_the_token_by_token_recurrence(chunk):
    from trlx_tpu.ops.ssd import ssd_chunked, ssd_step

    rs = np.random.RandomState(0)
    B, T, H, D = 2, 45, 4, 16
    q, k, v = (jnp.asarray(rs.randn(B, T, H, D), jnp.float32) for _ in range(3))
    mask = jnp.asarray(np.arange(T)[None, :] >= np.asarray([0, 6])[:, None], jnp.int32)
    A = -jnp.asarray(tf.alibi_slopes(H), jnp.float32)
    y, final = ssd_chunked(v, jnp.ones((1, T, H)), A, k, q, None, mask, None, chunk=chunk)
    S = jnp.zeros((B, H, D, D))
    want = []
    for t in range(T):
        S = jnp.exp(A)[None, :, None, None] * S + (v[:, t] * mask[:, t, None, None])[..., :, None] * k[:, t][..., None, :]
        want.append(jnp.einsum("bhpn,bhn->bhp", S, q[:, t]))
    np.testing.assert_allclose(y, jnp.stack(want, axis=1), rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(final, S, rtol=1e-5, atol=2e-5)
    # and the one-token step from the scan's state
    y1, S1 = ssd_step(final, v[:, 0], jnp.ones((1, H)), A, k[:, 0], q[:, 0], None)
    np.testing.assert_allclose(S1, jnp.exp(A)[None, :, None, None] * final + v[:, 0][..., :, None] * k[:, 0][..., None, :], rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(y1, jnp.einsum("bhpn,bhn->bhp", S1, q[:, 0]), rtol=1e-5, atol=2e-5)


def test_the_selection_keeps_its_forced_blocks_and_no_more_than_topk():
    rs = np.random.RandomState(1)
    B, T, H, KV, D = 2, 96, 4, 2, 16
    q = jnp.asarray(rs.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rs.randn(B, T, KV, D), jnp.float32)
    t = jnp.arange(T)[None, :]
    chosen = np.asarray(select_blocks(q, tf.pooled_keys(k, CFG), t, T // 8, CFG))  # [B, KV, T, 12]
    blocks = np.arange(T // 8)
    for pos in range(T):
        own = pos // 8
        assert not chosen[:, :, pos, own + 1 :].any()  # nothing past the query's own block
        assert chosen[:, :, pos, 0].all() and chosen[:, :, pos, max(pos - 11, 0) // 8 : own + 1].all()
        assert (chosen[:, :, pos].sum(-1) == min(own + 1, 5)).all()
    assert not (chosen[:, 0] == chosen[:, 1]).all()  # a set a KV head
    assert blocks.size == 12
    kept, causal = block_selected_pairs(T, CFG)
    assert kept == chosen[0, 0].repeat(8, axis=-1)[np.tril(np.ones((T, T), bool))].sum() and causal == T * (T + 1) / 2
    assert block_selected_pairs(24, CFG) == (300.0, 300.0)  # under dense_len: every causal pair
    assert block_selected_steps(80, 16, CFG) == pytest.approx(np.mean([5 / (p // 8 + 1) for p in range(80, 96)]))


@pytest.mark.parametrize("tile", [16, 32])
def test_block_selected_flash_kernels_match_the_reference(tile):
    from trlx_tpu.ops.flash_attention import attention_reference, flash_attention

    rs = np.random.RandomState(0)
    B, T, H, KV, D, blk = 2, 64, 4, 2, 16, 8
    q = jnp.asarray(rs.randn(B, T, H, D), jnp.float32)
    k, v = (jnp.asarray(rs.randn(B, T, KV, D), jnp.float32) for _ in range(2))
    mask = jnp.ones((B, T), jnp.int32).at[1, 50:].set(0)
    sel = jnp.asarray(rs.rand(B, KV, T, T // blk) > 0.5) | (jnp.arange(T)[:, None] // blk == jnp.arange(T // blk)[None, :])
    run = lambda q, k, v: flash_attention(q, k, v, mask, selection=sel, selection_block=blk, block_q=tile, block_k=tile, interpret=True)
    want = lambda q, k, v: attention_reference(q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2), mask,
                                               selection=sel, selection_block=blk)[0]
    np.testing.assert_allclose(run(q, k, v), want(q, k, v), atol=2e-5)
    w = jnp.asarray(rs.randn(B, T, H, D), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(run(*a) * w), argnums=(0, 1, 2))(q, k, v)
    ref_g = jax.grad(lambda *a: jnp.sum(want(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, ref_g):
        np.testing.assert_allclose(a, b, atol=5e-5)
    with pytest.raises(ValueError, match="selection_block"):
        flash_attention(q, k, v, mask, selection=sel[..., :-1], selection_block=blk, interpret=True)


def test_a_long_mlp_runs_in_pieces_with_the_same_result(monkeypatch):
    ids, mask = batch(24, [0, 3])
    whole = MODEL.apply({"params": PARAMS}, ids, attention_mask=mask)["logits"]
    monkeypatch.setattr(tf, "MLP_MAX_BYTES", 1024)
    monkeypatch.setattr(tf, "MLP_PIECE_BYTES", 12 * 128 * 4)
    assert tf.mlp_token_pieces(48, 128 * 4) == 4
    text = str(jax.make_jaxpr(lambda p: MODEL.apply({"params": p}, ids, attention_mask=mask)["logits"])(PARAMS))
    assert "f32[4,12,64]" in text  # four pieces of twelve tokens under lax.map
    np.testing.assert_allclose(MODEL.apply({"params": PARAMS}, ids, attention_mask=mask)["logits"], whole, atol=1e-5)


# ---------------------------------------------------------------------------
# the layout, the cache, the refusals, the configuration file
# ---------------------------------------------------------------------------


def test_the_preset_holds_the_published_layout_and_scalings():
    big = config_from_spec("builtin:minicpm-sala-9b")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        catalog = next(row for row in map(json.loads, f) if row["name"] == "MiniCPM-SALA")["config"]
    names = {"minicpm4": "attention", "lightning-attn": "lightning"}
    assert [names[m] for m in catalog["mixer_types"]] == [l.mixer for l in big.layer_layouts]
    assert [l.rotary for l in big.layer_layouts] == [l.mixer == "lightning" for l in big.layer_layouts]
    assert (big.embedding_multiplier, big.lm_head_multiplier) == (catalog["scale_emb"], catalog["dim_model_base"] / catalog["hidden_size"])
    assert big.residual_multiplier == pytest.approx(catalog["scale_depth"] / np.sqrt(catalog["num_hidden_layers"]))
    assert (big.sparse_topk, big.sparse_block, big.sparse_kernel, big.sparse_stride, big.sparse_window, big.sparse_dense_len) == (64, 64, 32, 16, 2048, 8192)
    assert hash(big) == hash(config_from_spec("builtin:minicpm-sala-9b"))
    with pytest.raises(ValueError, match="mixer_layout"):
        config_from_spec("builtin:minicpm-sala-test", mixer_layout=("attention", "mamba", "lightning", "attention"))
    with pytest.raises(NotImplementedError, match="scan_layers"):
        CausalTransformer(config_from_spec("builtin:minicpm-sala-test", scan_layers=True)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_the_cut_is_the_configuration_files_and_its_widths_check():
    from chipbench import job
    from trlx_tpu.data.configs import ModelConfig, ParallelConfig

    file = job.load_config("minicpm-sala-9b-l8")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        catalog = next(row for row in map(json.loads, f) if row["name"] == "MiniCPM-SALA")
    assert file["source"] == catalog["source_url"]
    for key, value in catalog["config"].items():  # every number of the catalog's config under the same key, but the reduced ones
        if key not in file["reduced"]:
            assert file["published"][key] == value, key
    assert sorted(file["reduced"]) == ["mixer_types", "num_hidden_layers"] == sorted(next(
        c["reduced"] for c in job.load_benchmark()["configs"] if c["name"] == "minicpm-sala-9b-l8"))
    assert file["published"]["mixer_types"] == catalog["config"]["mixer_types"][9:17]
    model = file["job"]["model"]
    cut = config_from_spec(model["model_path"], **model["model_extra_kwargs"])
    assert [l.mixer for l in cut.layer_layouts] == ["attention"] + ["lightning"] * 6 + ["attention"]
    assert cut.residual_multiplier == pytest.approx(1.4 / np.sqrt(32))  # the published depth, in the cut too
    cfg = types.SimpleNamespace(model=ModelConfig(**model), parallel=ParallelConfig(**file["job"]["parallel"]))
    job.check_published_widths(cfg, file)
    shapes = jax.eval_shape(lambda: CausalTransformer(cut).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert abs(n / 1e6 - 2820) < 2  # the configuration file's arithmetic
    sc = file["published"]["sparse_config"]
    assert (sc["topk"], sc["block_size"], sc["kernel_size"], sc["kernel_stride"], sc["init_blocks"], sc["window_size"], sc["dense_len"]) == (
        cut.sparse_topk, cut.sparse_block, cut.sparse_kernel, cut.sparse_stride, tf.SPARSE_INIT_BLOCKS, cut.sparse_window, cut.sparse_dense_len)
    cache = jax.eval_shape(lambda: make_kv_cache(cut, 4, 16384))
    assert cache[1]["state"].shape == (4, 32, 128, 128) and cache[1]["state"].dtype == jnp.float32
    assert cache[0]["kbar"].shape == (4, 2, 1024, 128) and cache[0]["k"].shape == (4, 16384, 2, 128)
    toy = job.load_config("minicpm-sala-9b-l8", toy=True)
    assert {k: toy["published"][k] for k in DIMS if k != "scale_depth"} == {k: v for k, v in DIMS.items() if k != "scale_depth"}
    assert toy["published"]["scale_depth"] == pytest.approx(DIMS["scale_depth"])


@pytest.mark.parametrize("path", ["slot_refill", "engine", "prefix_cache", "speculative"])
def test_kv_only_paths_refuse_the_new_layers_by_name(path):
    from trlx_tpu.ops.cache_layout import refuse

    with pytest.raises(NotImplementedError, match=rf"^{path} .*compressed keys.*B8c\); and a recurrence's state as the layer's whole cache \(leaves \['state'\]\): .*B7[bc]\); use the plain sampler"):
        refuse(jax.eval_shape(lambda: make_kv_cache(CFG, 1, 8)), path, 8)
    sparse_only = jax.eval_shape(lambda: make_kv_cache(config_from_spec("builtin:minicpm-sala-test", mixer_layout=("attention",) * 4), 1, 8))
    with pytest.raises(NotImplementedError, match=rf"^{path} .*cache holds compressed keys beside K and V \(leaves \['kbar'\]\): .*B8c\); use the plain sampler$"):
        refuse(sparse_only, path, 8)


@pytest.mark.parametrize("way", ["import", "export"])
def test_hf_interop_says_there_is_no_converter(way):
    from trlx_tpu.models.hf_interop import UnsupportedHFExport, config_from_hf, hf_config_from_transformer

    if way == "import":
        with pytest.raises(ValueError, match="minicpm_sala.*no HF checkpoint conversion.*B7"):
            config_from_hf(types.SimpleNamespace(model_type="minicpm_sala"))
    else:
        with pytest.raises(UnsupportedHFExport, match="minicpm_sala.*no HF checkpoint conversion"):
            hf_config_from_transformer(CFG)


def test_the_required_work_counts_both_kinds_of_layer():
    from chipbench.costs import minicpm_sala as costs

    big = config_from_spec("builtin:minicpm-sala-9b")
    assert costs.chosen_pairs(big, 16384) == block_selected_pairs(16384, big)[0] == 58335232.0
    assert costs.chosen_pairs(big, 4096) == 4096 * 4097 / 2 and costs.kernel_pairs(big, 4096) == 0.0
    assert costs.kernel_pairs(big, 16384) == sum(max((t - 31) // 16 + 1, 0) for t in range(16384))
    assert costs.scan_flops(big, 16384) == 4 * 32 * 128 * 128 * 16384
    tree = {"attn": {"q_proj": {"kernel": np.zeros((4096, 4096))}}, "mlp": {"up_proj": {"kernel": np.zeros((4096, 16384))}}}
    light = costs.layer_forward(big, 1, tree, 16384, {})
    assert light["mix"] == costs.scan_flops(big, 16384) and light["matmuls"][("attn", "q_proj", "kernel")] == 2.0 * 4096 * 4096 * 16384
    sparse = costs.layer_forward(big, 0, tree, 16384, {})
    scores = costs.selection_flops(big, 16384)
    forward = sum(sparse["matmuls"].values()) + sparse["mix"]
    assert forward == pytest.approx(2.0 * 16384 * (4096 * 4096 + 4096 * 16384) + costs.attention_flops(big, 16384) + scores)
    # the activation-gradient pass (matmuls once more, mix twice more) counts nothing for the selection
    assert sum(sparse["matmuls"].values()) + 2 * sparse["mix"] == pytest.approx(
        2.0 * 16384 * (4096 * 4096 + 4096 * 16384) + 2 * costs.attention_flops(big, 16384))


def test_forward_count_is_the_references_matmuls_in_both_kinds_of_layer(monkeypatch):
    """``chipbench/tests/test_flops.py``'s check of the forward count, for a
    stack whose layers are not all attention: the family's ``layer_forward``
    against the products in the reference's own jaxpr at the toy widths. The
    reference multiplies every projection, a sparse layer's full score square
    in blocks of its query rows, and of a lightning layer's recurrence the
    read-out alone as a product (its update ``k v^T`` is an outer product of
    two vectors: element-wise in a jaxpr), half of what the count requires."""
    import trlx_tpu.trainer.base as base
    from chipbench import flops
    from chipbench.checks import backbone_of
    from chipbench.costs import minicpm_sala as costs
    from chipbench.tests.test_flops import Q, R, _toy_trainer, dot_flops
    from trlx_tpu.parallel import make_mesh

    monkeypatch.setattr(base, "make_mesh", lambda parallel: make_mesh(parallel, devices=jax.devices()[:1]))
    trainer, config_file = _toy_trainer("minicpm-sala-9b-l8")
    model, tcfg, t = flops.Model(trainer, "minicpm_sala"), trainer.tcfg, Q + R
    assert model.layer_forward is costs.layer_forward and t < tcfg.sparse_dense_len
    square = float(-(-t // ref.Q_BLOCK) * ref.Q_BLOCK * t)
    expected = 0.0
    for i in range(model.n_layers):
        cost = model.layer(i, t, {})
        expected += sum(cost["matmuls"].values())
        if costs.is_lightning(tcfg, i):
            assert cost["mix"] == costs.scan_flops(tcfg, t)
            expected += cost["mix"] / 2
        else:
            assert cost["mix"] == 2.0 * 4 * 32 * t * (t + 1) / 2  # under dense_len: every causal pair, no selection
            expected += cost["mix"] * square / flops.pairs(t, None)
    expected += sum(2.0 * a * b * R for a, b in model.head.values())
    params = backbone_of(trainer.state.params)
    ids, mask = jnp.zeros((1, t), jnp.int32), jnp.ones((1, t), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, i, m: ref.logits(p, config_file["published"], i, m, (Q, t)))(params, ids, mask)
    assert dot_flops(jaxpr.jaxpr) == pytest.approx(expected, rel=0.02)


# ---------------------------------------------------------------------------
# no existing program moves
# ---------------------------------------------------------------------------

RECORDED = os.path.join(os.path.dirname(__file__), "fixtures", "programs_before_minicpm_sala.json")
RECORDED_FAMILIES = ("falconh1", "smallthinker", "glm", "k-exaone", "pangu")


def program_fingerprints(family):
    """sha256 of a toy preset's parameter tree, cache tree, and the jaxpr text
    of one train step (the gradient of a loss on the response's logits, with
    the hydra branch's input taken) and one decode step under two extents
    (float32, xla attention), on rows of 12 slots behind 3 pads: longer than
    the toys' window and ``index_topk`` of 8, so that rings, the learned
    selection and the scan's padding all trace."""
    cfg = config_from_spec(f"builtin:{family}-test", attention_impl="xla", **F32)
    model = CausalTransformer(cfg)
    ids = jnp.zeros((2, 12), jnp.int32)
    mask = jnp.ones((2, 12), jnp.int32).at[0, :3].set(0)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids)["params"])
    cache = jax.eval_shape(lambda: make_kv_cache(cfg, 2, 16))
    slots = jnp.ones((2, 16), jnp.int32)

    def loss(p):
        out = model.apply({"params": p}, ids, attention_mask=mask, branch_layer=1, logits_span=(8, 12))
        return jnp.mean(out["logits"] ** 2)

    texts = {
        "params": str(jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), params)),
        "cache": str(jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), cache)),
        "train": str(jax.make_jaxpr(jax.grad(loss))(params)),
        "decode": str(jax.make_jaxpr(
            lambda p, c: model.apply({"params": p}, ids[:, :1], attention_mask=slots, cache=c,
                                     cache_index=jnp.asarray(12, jnp.int32), kv_extents=(8, 16)))(params, cache)),
    }
    clean = lambda text: re.sub(r"0x[0-9a-f]+", "0x", text)
    return {k: hashlib.sha256(clean(v).encode()).hexdigest() for k, v in texts.items()}


@pytest.mark.parametrize("family", RECORDED_FAMILIES)
def test_presets_trace_to_the_programs_recorded_before_the_family(family, clean_trace_state):
    """Recorded on PR 49's parent by this function (``python
    tests/test_minicpm_sala.py`` there writes the file), before ``LayerLayout``
    gained the layer's mixer, ``make_kv_cache`` its two new kinds of layer,
    ``ops/ssd.py`` its optional steps and skip, ``MLP`` its pieces and the
    selected flash kernels their blocks: parameter tree, cache tree, train
    step and decode step byte for byte."""
    with jax.default_matmul_precision(None), open(RECORDED) as f:
        assert program_fingerprints(family) == json.load(f)[family]


# ---------------------------------------------------------------------------
# trlx_tpu.train(): the normal PPO path with adapters
# ---------------------------------------------------------------------------


def test_collection_counters_name_the_state_and_the_compressed_keys():
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.trainer.ppo import PPOTrainer

    cfg = default_ppo_config().evolve(
        tokenizer=dict(tokenizer_path="builtin:bytes"), train=dict(tracker=None),
        model=dict(model_path="builtin:minicpm-sala-test", num_layers_unfrozen=1),
        parallel=dict(param_dtype="float32", compute_dtype="float32"))
    trainer = PPOTrainer(cfg, reward_fn=lambda samples, **kw: [0.0] * len(samples))
    trainer._note_dense_kv_gauge((3, 40), GenerationConfig(max_new_tokens=16))
    assert trainer.last_cache_stats == {
        "rollout/kv_cache_bytes": float(2 * 2 * 3 * 56 * 2 * 16 * 4), "rollout/ssm_state_bytes": 0.0, "rollout/kv_lane_heads": 1.0,
        "rollout/linear_state_bytes": float(2 * 3 * 4 * 16 * 16 * 4),
        "rollout/kbar_cache_bytes": float(2 * 3 * 2 * 28 * 16 * 4),
        "rollout/attn_block_selected_frac": block_selected_steps(40, 16, CFG)}, trainer.last_cache_stats
    assert trainer.last_kv_layers == ((56, False),) * 2  # the two attention layers: a lightning layer has no slots


def test_train_runs_ppo_with_adapters_through_both_kinds_of_layer(tmp_path):
    """``trlx_tpu.train()`` with PPO, a value head, the hydra branch over the
    last block (a sparse one) and LoRA on q, k, v, o: rows of 48 slots, over
    the toy's ``dense_len`` of 32. Policy and branch start at KL 0; after two
    steps the last block's adapters and the value head have changed and
    nothing else has."""
    import trlx_tpu.trlx as trlx
    from trlx_tpu.data.default_configs import default_ppo_config

    config = default_ppo_config().evolve(
        train=dict(seq_length=48, batch_size=4, total_steps=2, eval_interval=10,
                   checkpoint_interval=10, epochs=1, save_best=False, tracker=None,
                   checkpoint_dir=str(tmp_path / "ckpts"), logging_dir=str(tmp_path / "logs")),
        model=dict(model_path="builtin:minicpm-sala-test", num_layers_unfrozen=1,
                   peft_kwargs=dict(peft_type="lora", r=4, lora_alpha=8,
                                    modified_modules=["q_proj", "k_proj", "v_proj", "o_proj"])),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        parallel=dict(param_dtype="float32", compute_dtype="float32"),
        method=dict(num_rollouts=8, chunk_size=8, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=12, min_new_tokens=12, top_k=0, top_p=1.0, do_sample=True)),
    )
    records, before = [], {}

    def hook(trainer):
        trainer.tracker = types.SimpleNamespace(
            log=lambda stats, step=None: records.append(dict(stats)), finish=lambda: None)
        before.update(params=jax.tree_util.tree_map(np.asarray, trainer.state.params))
        generate = trainer.generate

        def capturing(*a, **kw):  # as chipbench/run.py keeps the sampler's own record
            out = generate(*a, **kw)
            before.setdefault("gen", out)
            before.setdefault("gen_params", trainer.state.params)
            return out

        trainer.generate = capturing

    rng = np.random.RandomState(0)
    prompts = ["".join(chr(97 + c) for c in rng.randint(0, 26, size=36)) for _ in range(8)]
    trainer = trlx.train(
        reward_fn=lambda samples, prompts, outputs, **kw: [float(i % 4) for i, _ in enumerate(outputs)],
        prompts=prompts, config=config, init_trainer_hook=hook)
    assert trainer.tcfg.model_type == "minicpm_sala" and trainer.tcfg.lora_r == 4
    collection = next(r for r in records if "time/exp" in r)
    assert float(collection.get("policy/sqrt_kl", collection.get("policy/sqrt_ref_kl"))) < 1e-6
    S = int(collection["rollout/kv_cache_bytes"] // (2 * 2 * 8 * 2 * 16 * 4))
    assert 48 <= S <= 56
    assert collection["rollout/linear_state_bytes"] == 2 * 8 * 4 * 16 * 16 * 4
    assert collection["rollout/kbar_cache_bytes"] == 2 * 8 * 2 * (S // 2) * 16 * 4
    assert 0.5 < collection["rollout/attn_block_selected_frac"] < 1.0
    assert 0.0 < collection["rollout/kv_read_frac"] <= 1.0
    step = next(r for r in records if "time/train_step" in r)
    width = int(step["learn/step_width"])
    kept, causal = block_selected_pairs(width, trainer.tcfg)
    assert step["learn/attn_block_selected_frac"] == pytest.approx(kept / causal) and kept < causal
    assert np.isfinite([v for k, v in step.items() if k.startswith("losses/")]).all()
    changed = set()
    after = jax.tree_util.tree_map(np.asarray, trainer.state.params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(after), jax.tree_util.tree_leaves(before["params"])):
        if not np.array_equal(a, b):
            changed.add(jax.tree_util.keystr(path))
    assert changed and all("v_head" in k or ("['h_3']" in k and "lora_" in k) for k in changed), changed
    assert any("lora_b" in k for k in changed) and any("v_head" in k for k in changed)
    # chipbench/state_check.py on the trainer's own module (value head, adapters) and the sampler's own rollouts
    from chipbench import state_check

    got = state_check.state_readings(trainer, {"family": "minicpm_sala", "published": dict(DIMS, lora_alpha=8.0)}, before["gen"])
    assert got["state_layers"] == [1, 2] and got["state_steps"] == 12
    assert max(got["state_rel_l2_prefill"], got["state_rel_l2_decode"]) < 2e-5, got


if __name__ == "__main__":  # the recorder
    with jax.default_matmul_precision(None):
        print(json.dumps({f: program_fingerprints(f) for f in RECORDED_FAMILIES}, indent=1))
