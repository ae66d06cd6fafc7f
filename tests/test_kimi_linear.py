"""Kimi-Linear (``model_type`` ``kimi_linear``): Kimi Delta Attention layers
(a gated delta rule under a decay a channel, behind short convs) whose state
and conv rows are the layer's whole cache, beside latent-attention layers with
no query latent and no rotary embedding, over held experts, against the plain
reference (``chipbench/reference/kimi_linear.py``) at toy widths on the CPU.

``builtin:kimi-linear-test``: a dense KDA layer, two KDA expert layers, a
latent expert layer; KDA heads of 24, q/k 20 =
12 + 8, v 16, 8 experts.
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
from chipbench.reference import kimi_linear as ref
from trlx_tpu.models.transformer import CausalTransformer, config_from_spec, make_kv_cache
from trlx_tpu.ops.delta_rule import kda_chunked, kda_step
from trlx_tpu.ops.sampling import GenerationConfig

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
CFG = config_from_spec("builtin:kimi-linear-test", **F32)
MODEL = CausalTransformer(CFG)
DIMS = dict(
    num_attention_heads=4, qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=16,
    rms_norm_eps=1e-5, rope_theta=10000, num_hidden_layers=4, hidden_size=64, num_experts_per_token=2,
    routed_scaling_factor=2.446, num_experts=8,
    linear_attn_config=dict(kda_layers=[1, 2, 3], full_attn_layers=[4], head_dim=24, num_heads=2, short_conv_kernel_size=4),
)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def seeded(params, seed=0):
    """Weights at which every mechanism shows: matrices of unit gain, norm
    scales scattered about 1, a selection bias as large as the scores' spread."""

    def leaf(path, x):
        name = jax.tree_util.keystr(path)
        rs = np.random.RandomState(int(hashlib.sha256(f"{seed}{name}".encode()).hexdigest()[:8], 16))
        if name.endswith("['scale']") or name.endswith("['o_norm_scale']"):
            return jnp.asarray(1.0 + 0.3 * rs.randn(*x.shape), x.dtype)
        if name.endswith("['router_bias']"):
            return jnp.asarray(0.2 * rs.randn(*x.shape), x.dtype)
        if name.endswith("['kernel']") or "lora_" in name or x.ndim == 3:
            return jnp.asarray(rs.randn(*x.shape) / np.sqrt(x.shape[-2]), x.dtype)
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
@pytest.mark.parametrize("T", [24, 70], ids=["inside_one_chunk", "two_chunks"])
def test_logits_match_the_reference_on_left_padded_rows_of_unlike_length(T, impl):
    ids, mask = batch(T, [0, 5, 11])
    model = CausalTransformer(config_from_spec("builtin:kimi-linear-test", attention_impl=impl, **F32))
    got = model.apply({"params": PARAMS}, ids, attention_mask=mask)["logits"]
    want = ref.logits(PARAMS, DIMS, ids, mask, (0, T))
    assert rel(got, want, mask) < 2e-5


def test_a_rows_logits_do_not_depend_on_the_padding_in_front_of_it():
    """A padded slot feeds nothing into the conv window or the state and does
    not decay it: both are zero until the row's first real slot."""
    T, pad = 40, 13
    ids, mask = batch(T, [pad])
    padded = MODEL.apply({"params": PARAMS}, ids, attention_mask=mask)["logits"][0, pad:]
    alone = MODEL.apply({"params": PARAMS}, ids[:, pad:], attention_mask=mask[:, pad:])["logits"][0]
    assert float(jnp.abs(padded - alone).max()) < 2e-5 * float(jnp.abs(alone).max())


@pytest.mark.parametrize("P", [30, 33], ids=["prefill_inside_a_chunk", "prefill_past_a_chunk"])
def test_prefill_then_decode_through_state_conv_rows_and_latent_cache_matches_the_full_forward(P):
    """The sampler's two programs: a span from slot 0 through the chunked
    delta rule and the expanded latent form, then single tokens through
    ``kda_step``, the conv rows and the absorbed form with un-rotated keys,
    against the reference's full forward."""
    T = 44
    ids, mask = batch(T, [0, 7, 2])
    want = ref.logits(PARAMS, DIMS, ids, mask, (0, T))
    cache = make_kv_cache(CFG, 3, T, jnp.float32)
    assert set(cache[0]) == {"state", "conv"} and set(cache[3]) == {"ckv", "k_rope"}
    out = MODEL.apply({"params": PARAMS}, ids[:, :P], attention_mask=mask, cache=cache, cache_index=0)
    logits, cache = [out["logits"]], out["cache"]
    for t in range(P, T):
        out = MODEL.apply({"params": PARAMS}, ids[:, t : t + 1], attention_mask=mask, cache=cache, cache_index=t)
        logits.append(out["logits"])
        cache = out["cache"]
    assert rel(jnp.concatenate(logits, axis=1), want, mask) < 2e-5
    assert cache[1]["state"].dtype == jnp.float32 and cache[1]["conv"].shape == (3, 3, 3 * 2 * 24)


@pytest.mark.parametrize("fault", [None, "bf16_state"], ids=["float32_state", "bf16_state_fault"])
def test_the_samplers_cached_state_and_conv_rows_are_the_references(fault):
    """``chipbench/kda_state_check.py``'s reading, at toy widths in float32: the
    sampler's prefill and one-token steps on left-padded rows leave every KDA
    layer's ``state`` and ``conv`` rows at the reference's (``kda_states`` of the
    inputs that layer saw) to rounding, after the prefill and after the steps;
    a clean run's own controls (``S`` rounded to bfloat16 after every token, the
    rows to float8) stand a hundred times further off on every reading, and the
    check's verdict holds a reading to its limits."""
    from chipbench import kda_state_check

    P, N = 40, 24
    ids, mask = batch(P + N, [0, 5, 11, 2])
    gen = types.SimpleNamespace(sequences=ids, prompt_mask=mask[:, :P], response_mask=jnp.ones((4, N), jnp.int32),
                                response_tokens=ids[:, P:])
    trainer = types.SimpleNamespace(state=types.SimpleNamespace(params=PARAMS), module=MODEL, tcfg=CFG)
    got = kda_state_check.state_readings(trainer, {"family": "kimi_linear", "published": DIMS}, gen, fault=fault)
    assert (got["kda_layers"], got["kda_rows"], got["kda_steps"]) == ([0, 1, 2], 4, N)
    names = [f"kda_{leaf}_rel_l2_{when}" for leaf in ("state", "conv") for when in ("prefill", "decode")]
    limits = dict.fromkeys(names, 2e-5)
    if fault is None:
        assert kda_state_check.verdict(got, limits), got
        control = got["control"]
        assert all(control[k] > 2e-3 for k in names) and not kda_state_check.verdict(control, limits), control
    else:  # the fault is in the state alone: the rows stay the reference's
        assert "control" not in got and not kda_state_check.verdict(got, limits), got
        assert min(got[k] for k in names[:2]) > 2e-3 and max(got[k] for k in names[2:]) < 2e-5, got
    assert set(kda_state_check.load_limits("kimi-linear-48b-a3b-l8e32")) == set(names)


@pytest.mark.parametrize("fault", ref.FAULTS + ref.PRECISION_CONTROLS)
def test_every_planted_fault_of_the_reference_is_caught(fault):
    """Each other reading of what the catalog row does not settle, planted in
    the reference, moves the float32 logits well past the agreement above
    (2e-5). ``bf16_state`` is the mildest, a control for precision."""
    T = 70
    ids, mask = batch(T, [0, 5, 11])
    got = MODEL.apply({"params": PARAMS}, ids, attention_mask=mask)["logits"]
    moved = rel(got, ref.logits(PARAMS, DIMS, ids, mask, (0, T), fault=fault), mask)
    assert not moved < (2e-4 if fault == "bf16_state" else 5e-3), (fault, moved)  # (a fault that blows up reads nan: caught)


def test_one_ppo_step_under_lora_has_the_references_loss_and_gradients():
    """A clipped PPO objective on the response's logprobs with adapters (r 4,
    B not zero) on q, k, v, o of the KDA layers and q, kv_a, o of the latent
    one: the loss and the gradients with respect to every adapter, through
    three chunked delta rules, their convs and gates, against the reference's
    token-by-token recurrence differentiated by jax."""
    cfg = config_from_spec("builtin:kimi-linear-test", lora_r=4, lora_alpha=8.0,
                           lora_targets=("q_proj", "k_proj", "v_proj", "kv_a_proj", "o_proj"), **F32)
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
    assert len(got) == (3 * 4 + 3) * 2
    for name in got:
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0 and float(jnp.abs(got[name] - want[name]).max()) < 2e-4 * scale + 1e-8, name


# ---------------------------------------------------------------------------
# the chunked delta rule, its step, its pieces, its statistics
# ---------------------------------------------------------------------------


def delta_inputs(gate, T=150, B=2, H=3, K=16, V=8):
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(keys[0], (B, T, H, K))
    k = jax.random.normal(keys[1], (B, T, H, K))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (B, T, H, V))
    if gate == "strongest":  # A 16, softplus input +6: about -96 a TOKEN, a chunk's decay far past -88
        g = -16.0 * jax.nn.softplus(6.0 + 0.1 * jax.random.normal(keys[3], (B, T, H, K)))
    else:  # the configuration's range: -0.001 to -1.6 a channel a token
        g = -jnp.exp(jax.random.uniform(keys[3], (B, T, H, K), minval=np.log(1e-3), maxval=np.log(1.6)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (B, T, H)))
    mask = jnp.ones((B, T), jnp.int32).at[0, :7].set(0)
    return q, k, v, g, beta, mask, jax.random.normal(keys[5], (B, H, K, V))


def token_by_token(q, k, v, g, beta, mask, s0):
    """``kda_step`` over the tokens: the recurrence as written."""
    def step(S, x):
        q_t, k_t, v_t, g_t, b_t, m = x
        o, S = kda_step(S, q_t, k_t, v_t, g_t * m[:, None, None], b_t * m[:, None])
        return S, o

    S, o = jax.lax.scan(step, s0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta, mask.astype(jnp.float32))))
    return jnp.moveaxis(o, 0, 1), S


@pytest.mark.parametrize("gate", ["configured", "strongest"])
@pytest.mark.parametrize("chunk", [32, 64])
def test_the_chunked_delta_rule_is_the_token_by_token_recurrence(chunk, gate):
    """Outputs, final state and gradients, from a non-zero initial state, on
    rows with padding in front and a last chunk that is not whole; under the
    strongest gate the parameterisation gives, every value finite."""
    q, k, v, g, beta, mask, s0 = delta_inputs(gate)
    m = mask[:, :, None, None]
    want_o, want_S = token_by_token(q, k, v, g, beta, mask, s0)
    chunked = lambda q, k, v, g, beta, mask, s0: kda_chunked(  # a padded token is the caller's to mask, as `KDAMixer` does
        q, k, v, g * mask[:, :, None, None], beta * mask[:, :, None], s0, chunk=chunk)
    got_o, got_S = chunked(q, k, v, g, beta, mask, s0)
    assert bool(jnp.isfinite(got_o).all()) and bool(jnp.isfinite(got_S).all())
    assert float(jnp.abs((got_o - want_o) * m).max()) < 2e-5 * float(jnp.abs(want_o * m).max())
    assert float(jnp.abs(got_S - want_S).max()) < 2e-5 * max(float(jnp.abs(want_S).max()), 1e-3)

    def grads(fn):
        def loss(q, k, v, g, beta, s0):
            o, S = fn(q, k, v, g, beta, mask, s0)
            return jnp.sum((o * m) ** 2) + jnp.sum(S**2)

        return jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))(q, k, v, g, beta, s0)

    for name, got, want in zip("q k v g beta s0".split(), grads(chunked), grads(token_by_token)):
        assert bool(jnp.isfinite(got).all()), name
        assert float(jnp.abs(got - want).max()) < 5e-5 * float(jnp.abs(want).max()) + 1e-9, name


def test_the_step_is_the_references_recurrence():
    q, k, v, g, beta, mask, _ = delta_inputs("configured", T=40, B=1)
    want_o, want_S = ref.delta_rule(q[0], k[0], v[0], g[0], beta[0])
    got_o, got_S = token_by_token(q, k, v, g, beta, jnp.ones_like(mask), jnp.zeros((1, 3, 16, 8)))
    assert float(jnp.abs(got_o[0] - want_o).max()) < 1e-5 and float(jnp.abs(got_S[0] - want_S).max()) < 1e-5


def test_a_long_pass_runs_in_pieces_of_whole_rows_with_the_same_result(monkeypatch):
    """Past ``KDA_MAX_TOKENS`` the mixer runs pieces of whole rows one after
    another: the same logits, the same statistics, and through the sampler's
    prefill the same state and conv rows."""
    T = 40
    ids, mask = batch(T, [0, 5, 11, 2])
    whole = MODEL.apply({"params": PARAMS}, ids, attention_mask=mask)
    cache = make_kv_cache(CFG, 4, T, jnp.float32)
    whole_cache = MODEL.apply({"params": PARAMS}, ids, attention_mask=mask, cache=cache, cache_index=0)["cache"]
    monkeypatch.setattr(tf, "KDA_MAX_TOKENS", 2 * T)
    assert tf.latent_row_pieces(4, T, tf.KDA_MAX_TOKENS) == 2
    pieces = MODEL.apply({"params": PARAMS}, ids, attention_mask=mask)
    assert float(jnp.abs(pieces["logits"] - whole["logits"]).max()) < 1e-5
    np.testing.assert_allclose(pieces["kda_stats"], whole["kda_stats"], rtol=1e-6)
    pieces_cache = MODEL.apply({"params": PARAMS}, ids, attention_mask=mask, cache=cache, cache_index=0)["cache"]
    for a, b in zip(jax.tree_util.tree_leaves(pieces_cache), jax.tree_util.tree_leaves(whole_cache)):
        assert a.shape == b.shape and float(jnp.abs(a - b).max()) < 1e-5


def test_a_pass_reports_its_most_negative_chunk_decay_and_its_mean_beta():
    """``learn/kda_log_decay_min`` and ``learn/kda_beta_mean``: from the
    layers' own gates, recomputed here from the reference's formulas."""
    T = 70
    ids, mask = batch(T, [0, 9])
    out = MODEL.apply({"params": PARAMS}, ids, attention_mask=mask)
    assert "kda_stats" in out and out["kda_stats"].shape == (2,)
    low, betas, x = [], [], PARAMS["wte"]["embedding"][ids]
    for l in range(3):  # the three KDA layers' inputs, by running the reference layer by layer
        p = PARAMS[f"h_{l}"]
        u = ref._rms_norm(x, p["ln_attn"]["scale"], 1e-5) * mask[..., None]
        f = (u @ p["attn"]["f_a_proj"]["kernel"]) @ p["attn"]["f_b_proj"]["kernel"] + p["attn"]["dt_bias"]
        g = -jnp.exp(p["attn"]["A_log"])[None, None, :, None] * jax.nn.softplus(f).reshape(2, T, 2, 24) * mask[..., None, None]
        g = jnp.pad(g, ((0, 0), (0, -T % 64), (0, 0), (0, 0))).reshape(2, -1, 64, 2, 24)
        low.append(float(jnp.min(jnp.sum(g, axis=2))))
        betas.append(jax.nn.sigmoid(u @ p["attn"]["b_proj"]["kernel"]))
        statics = dict(heads=4, nope=12, rope=8, v_dim=16, eps=1e-5, theta=1e4, kda_heads=2, kda_dim=24, top_k=2, scaling=2.446, first=0)
        x = jnp.stack([ref._layer(p, x[b], mask[b], jnp.arange(T), **statics) for b in range(2)])
    beta_mean = sum(float(jnp.sum(jnp.mean(b, axis=-1) * mask)) for b in betas) / (3 * float(mask.sum()))
    assert float(out["kda_stats"][0]) == pytest.approx(min(low), rel=1e-4) and min(low) < -1.0
    assert float(out["kda_stats"][1]) == pytest.approx(beta_mean, rel=1e-4)
    cache = make_kv_cache(CFG, 2, T, jnp.float32)
    assert "kda_stats" not in MODEL.apply({"params": PARAMS}, ids, attention_mask=mask, cache=cache, cache_index=0)


# ---------------------------------------------------------------------------
# the shares, the preset, the configuration file, the refusals
# ---------------------------------------------------------------------------


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The guide's section 4: a layer that holds experts ``[first, first +
    held)`` of the router's width returns the part its own give; the shares
    of all chips, the shared expert counted once, are the uncut layer. Toy:
    8 experts in 4 shares of 2; program and reference alike."""
    whole = config_from_spec("builtin:kimi-linear-test", **F32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 64))
    mlp = init(CausalTransformer(whole), seed=4)["h_1"]["mlp"]
    full, _ = tf.MoEMLP(whole).apply({"params": mlp}, x)
    routed_ref, shared_ref = ref.moe_layer(mlp, x, 2, 2.446)
    assert rel(full, routed_ref + shared_ref, np.ones((2, 9))) < 2e-5
    total = 0.0
    for first in range(0, 8, 2):
        cut = config_from_spec("builtin:kimi-linear-test", moe_experts_held=2, moe_first_expert=first, **F32)
        held = dict(mlp, **{k: mlp[k][first : first + 2] for k in ("w_gate", "w_up", "w_down")})
        share, _ = tf.MoEMLP(cut).apply({"params": held}, x)
        routed, shared = ref.moe_layer(held, x, 2, 2.446, first=first)
        assert rel(share, routed + shared, np.ones((2, 9))) < 2e-5
        total = total + (share - shared)
    assert rel(total + shared_ref, full, np.ones((2, 9))) < 2e-5


def test_the_preset_holds_the_published_layout():
    big = config_from_spec("builtin:kimi-linear-48b-a3b")
    with open(CATALOG) as f:
        catalog = next(row for row in map(json.loads, f) if row["name"] == "Kimi-Linear-48B-A3B-Instruct")["config"]
    linear = catalog["linear_attn_config"]
    assert [i + 1 for i, l in enumerate(big.layer_layouts) if l.mixer == "kda"] == linear["kda_layers"]
    assert [i + 1 for i, l in enumerate(big.layer_layouts) if l.mixer == "attention"] == linear["full_attn_layers"]
    assert not any(l.rotary for l in big.layer_layouts)  # mla_use_nope
    assert [l.ffn for l in big.layer_layouts] == ["dense"] + ["moe"] * 26
    assert (big.kda_heads, big.kda_head_dim, big.kda_conv) == (linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"])
    assert (big.q_lora_rank, big.kv_lora_rank, big.dims_per_head, big.v_dims_per_head) == (0, 512, 192, 128)
    assert (big.num_experts, big.num_experts_per_tok, big.routed_scaling_factor) == (256, 8, 2.446)
    assert hash(big) == hash(config_from_spec("builtin:kimi-linear-48b-a3b"))
    with pytest.raises(ValueError, match="mixer_layout"):
        config_from_spec("builtin:kimi-linear-test", kda_heads=0)
    with pytest.raises(ValueError, match="query latent"):
        config_from_spec("builtin:glm-test", q_lora_rank=0)
    with pytest.raises(NotImplementedError, match="scan_layers"):
        CausalTransformer(config_from_spec("builtin:kimi-linear-test", scan_layers=True)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_the_cut_is_the_configuration_files_and_its_widths_check():
    from chipbench import job
    from trlx_tpu.data.configs import ModelConfig, ParallelConfig

    file = job.load_config("kimi-linear-48b-a3b-l8e32")
    with open(CATALOG) as f:
        catalog = next(row for row in map(json.loads, f) if row["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert file["source"] == catalog["source_url"]
    for key, value in catalog["config"].items():  # every number of the catalog's config under the same key, but the reduced ones
        if key not in file["reduced"]:
            assert file["published"][key] == value, key
    reduced = ["linear_attn_config", "num_experts", "num_hidden_layers", "vocab_size"]
    assert sorted(file["reduced"]) == reduced == sorted(next(
        c["reduced"] for c in job.load_benchmark()["configs"] if c["name"] == "kimi-linear-48b-a3b-l8e32"))
    linear, published = file["published"]["linear_attn_config"], catalog["config"]["linear_attn_config"]
    assert linear == dict(published, kda_layers=[l for l in published["kda_layers"] if l <= 8], full_attn_layers=[4, 8])
    model = file["job"]["model"]
    cut = config_from_spec(model["model_path"], **model["model_extra_kwargs"])
    assert [l.mixer for l in cut.layer_layouts] == ["kda", "kda", "kda", "attention"] * 2
    assert (cut.num_experts, cut.experts_held, cut.moe_first_expert) == (file["router_width"], 32, 0)
    cfg = types.SimpleNamespace(model=ModelConfig(**model), parallel=ParallelConfig(**file["job"]["parallel"]))
    job.check_published_widths(cfg, file)
    shapes = jax.eval_shape(lambda: CausalTransformer(cut).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert abs(n / 1e6 - 2092.6) < 0.5  # the configuration file's arithmetic
    cache = jax.eval_shape(lambda: make_kv_cache(cut, 32, 4096))
    assert cache[0]["state"].shape == (32, 32, 128, 128) and cache[0]["state"].dtype == jnp.float32
    assert cache[0]["conv"].shape == (32, 3, 12288) and cache[3]["ckv"].shape == (32, 4096, 512) and cache[3]["k_rope"].shape == (32, 4096, 64)
    traffic = job.load_json("traffic", "ppo_ctx4k_r32")
    assert traffic["job"]["model"]["num_layers_unfrozen"] == 2 and traffic["job"]["method"]["chunk_size"] == 32
    toy = job.load_config("kimi-linear-48b-a3b-l8e32", toy=True)
    for key, value in DIMS.items():
        if key != "num_experts":  # the toy of the benchmark holds 4 of its 8
            assert toy["published"][key] == value, key


@pytest.mark.parametrize("path", ["slot_refill", "engine", "prefix_cache", "speculative"])
def test_kv_only_paths_refuse_the_new_layers_by_name(path):
    from trlx_tpu.ops.cache_layout import refuse

    cache = jax.eval_shape(lambda: make_kv_cache(CFG, 1, 8))
    with pytest.raises(NotImplementedError, match=rf"^{path} .*a recurrence's state as the layer's whole cache \(leaves \['conv', 'state'\]\): .*B7[bc]\)"):
        refuse(cache, path, 8)
    with pytest.raises(NotImplementedError, match=rf"^{path} .*a latent in place of K and V \(leaves \['ckv', 'k_rope'\]\): .*B4[ab]\)"):
        refuse(cache, path, 8)


@pytest.mark.parametrize("way", ["import", "export"])
def test_hf_interop_says_there_is_no_converter(way):
    from trlx_tpu.models.hf_interop import UnsupportedHFExport, config_from_hf, hf_config_from_transformer

    if way == "import":
        with pytest.raises(ValueError, match="kimi_linear.*no HF checkpoint conversion.*B7"):
            config_from_hf(types.SimpleNamespace(model_type="kimi_linear"))
    else:
        with pytest.raises(UnsupportedHFExport, match="kimi_linear.*no HF checkpoint conversion"):
            hf_config_from_transformer(CFG)


# ---------------------------------------------------------------------------
# required work (chipbench/costs/kimi_linear.py)
# ---------------------------------------------------------------------------


def test_the_required_work_counts_the_three_kinds_of_layer():
    from chipbench import flops
    from chipbench.costs import kimi_linear as costs

    shapes = jax.eval_shape(lambda: MODEL.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    t = 50
    dense_kda = costs.layer_forward(CFG, 0, shapes["h_0"], t, {})
    expert_kda = costs.layer_forward(CFG, 1, shapes["h_1"], t, {"moe/held_frac": 0.5})
    latent = costs.layer_forward(CFG, 3, shapes["h_3"], t, {"moe/held_frac": 0.5})
    assert dense_kda["mix"] == expert_kda["mix"] == 6 * 2 * 24 * 24 * t  # three d x d products a head a token
    assert latent["mix"] == 2 * 4 * (20 + 16) * flops.pairs(t, None)  # q/k 20, v 16 on the causal pairs
    assert dense_kda["matmuls"][("attn", "conv_weight")] == 2 * 4 * 3 * 48 * t
    assert expert_kda["matmuls"][("mlp", "w_up")] == 2 * 64 * 32 * 2 * 0.5 * t  # k = 2, half of them held
    assert ("attn", "q_proj", "kernel") in latent["matmuls"] and ("attn", "f_b_proj", "kernel") in dense_kda["matmuls"]
    model = types.SimpleNamespace(tcfg=CFG, n_layers=4, lowest_trained=2, ref_layers=[2, 3], epochs=1, act_bytes=4)
    cycle = {"row_lengths": [(30, 10)] * 4, "steps": [{}] * 2}
    step = costs.kda_step(model, cycle)
    assert step == [{"phase": "decode", "flops": 3 * 4 * 9 * 6.0 * 2 * 24 * 24, "bytes": 3 * 4 * 9 * 2.0 * 4 * 2 * 24 * 24}]
    scan = {p["phase"]: p for p in costs.kda_scan(model, cycle)}
    assert scan["prefill"]["flops"] == 3 * 4 * 6.0 * 2 * 24 * 24 * 30 and scan["score_reference"]["flops"] == 1 * 4 * 6.0 * 2 * 24 * 24 * 40
    assert scan["train_backward"]["flops"] == 2 * scan["score_reference"]["flops"]  # one KDA layer at or above the lowest trained leaf
    assert [p["phase"] for p in costs.flash_fwd(model, cycle)] == ["prefill", "score", "score_reference", "train_forward"]
    assert costs.flash_fwd(model, cycle)[1]["flops"] == 4 * latent["mix"] / flops.pairs(t, None) * flops.pairs(40, None)


# ---------------------------------------------------------------------------
# no existing program moves
# ---------------------------------------------------------------------------

RECORDED = os.path.join(os.path.dirname(__file__), "fixtures", "programs_before_kimi_linear.json")
RECORDED_FAMILIES = ("pangu", "glm", "falconh1", "minicpm-sala")


def program_fingerprints(family):
    """sha256 of a toy preset's parameter tree, cache tree, and the jaxpr text
    of one train step (the gradient of a loss on the response's logits, with
    the hydra branch's input taken) and one decode step under two extents
    (float32, xla attention), on rows of 12 slots behind 3 pads. The test
    that calls it takes ``clean_trace_state`` (``tests/conftest.py``): a jaxpr's
    text also depends on the matmul precision and the global mesh that other
    tests of the worker leave behind."""
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
    """Recorded on PR 54's parent by this function (``python
    tests/test_kimi_linear.py`` there writes the file), before ``LayerLayout``
    gained the ``kda`` mixer, ``make_kv_cache`` its new kind of layer,
    ``LatentAttention`` its form without a query latent or rotary embedding,
    ``causal_conv`` its optional bias and ``Block`` the KDA statistics:
    parameter tree, cache tree, train step and decode step byte for byte."""
    with open(RECORDED) as f:
        assert program_fingerprints(family) == json.load(f)[family]


# ---------------------------------------------------------------------------
# trlx_tpu.train(): the normal PPO path with adapters
# ---------------------------------------------------------------------------


def test_collection_counters_name_the_state_with_its_conv_rows_and_the_latents():
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.trainer.ppo import PPOTrainer

    cfg = default_ppo_config().evolve(
        tokenizer=dict(tokenizer_path="builtin:bytes"), train=dict(tracker=None),
        model=dict(model_path="builtin:kimi-linear-test", num_layers_unfrozen=2),
        parallel=dict(param_dtype="float32", compute_dtype="float32"))
    trainer = PPOTrainer(cfg, reward_fn=lambda samples, **kw: [0.0] * len(samples))
    trainer._note_dense_kv_gauge((3, 40), GenerationConfig(max_new_tokens=16))
    stats = trainer.last_cache_stats
    assert stats["rollout/linear_state_bytes"] == float(3 * 3 * (2 * 24 * 24 + 3 * 3 * 2 * 24) * 4)  # state and conv rows, three layers
    assert stats["rollout/latent_cache_bytes"] == float(3 * 56 * (16 + 8) * 4)  # one latent layer
    assert stats["rollout/kv_cache_bytes"] == 0.0 and stats["rollout/ssm_state_bytes"] == 0.0, stats
    assert trainer.last_kv_layers == ((56, False),)  # the latent layer: a KDA layer has no slots


def test_train_runs_ppo_with_adapters_through_both_kinds_of_layer(tmp_path):
    """``trlx_tpu.train()`` with PPO, a value head, the hydra branch over the
    last TWO blocks (a KDA layer and a latent one) and LoRA: policy and branch
    start at KL 0; after two steps the two unfrozen blocks' adapters and the
    value head have changed and nothing else has; the step records carry the
    KDA layers' statistics and the collection records their state."""
    import trlx_tpu.trlx as trlx
    from trlx_tpu.data.default_configs import default_ppo_config

    config = default_ppo_config().evolve(
        train=dict(seq_length=48, batch_size=4, total_steps=2, eval_interval=10,
                   checkpoint_interval=10, epochs=1, save_best=False, tracker=None,
                   checkpoint_dir=str(tmp_path / "ckpts"), logging_dir=str(tmp_path / "logs")),
        model=dict(model_path="builtin:kimi-linear-test", num_layers_unfrozen=2,
                   model_extra_kwargs=dict(moe_experts_held=4),
                   peft_kwargs=dict(peft_type="lora", r=4, lora_alpha=8,
                                    modified_modules=["q_proj", "k_proj", "v_proj", "kv_a_proj", "o_proj"])),
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

    rng = np.random.RandomState(0)
    prompts = ["".join(chr(97 + c) for c in rng.randint(0, 26, size=36)) for _ in range(8)]
    trainer = trlx.train(
        reward_fn=lambda samples, prompts, outputs, **kw: [float(i % 4) for i, _ in enumerate(outputs)],
        prompts=prompts, config=config, init_trainer_hook=hook)
    assert trainer.tcfg.model_type == "kimi_linear" and trainer.tcfg.lora_r == 4
    collection = next(r for r in records if "time/exp" in r)
    assert float(collection.get("policy/sqrt_kl", collection.get("policy/sqrt_ref_kl"))) < 1e-6
    assert collection["rollout/linear_state_bytes"] == 3 * 8 * (2 * 24 * 24 + 3 * 3 * 2 * 24) * 4
    assert collection["rollout/latent_cache_bytes"] > 0 and collection["rollout/kv_cache_bytes"] == 0
    step = next(r for r in records if "time/train_step" in r)
    assert step["learn/kda_log_decay_min"] < 0.0 and 0.0 < step["learn/kda_beta_mean"] < 1.0
    assert step["learn/kda_scan_pallas"] == 0.0  # heads of 24: the jax.numpy form (heads of whole lanes take the kernel: tests/test_delta_rule_kernel.py)
    assert 0.0 < step["moe/held_frac"] < 1.0
    assert np.isfinite([v for k, v in step.items() if k.startswith("losses/")]).all()
    changed = set()
    after = jax.tree_util.tree_map(np.asarray, trainer.state.params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(after), jax.tree_util.tree_leaves(before["params"])):
        if not np.array_equal(a, b):
            changed.add(jax.tree_util.keystr(path))
    assert changed and all("v_head" in k or (("['h_2']" in k or "['h_3']" in k) and "lora_" in k) for k in changed), changed
    assert any("['h_2']" in k for k in changed) and any("['h_3']" in k for k in changed) and any("v_head" in k for k in changed)


if __name__ == "__main__":  # the recorder
    with jax.default_matmul_precision(None):
        print(json.dumps({f: program_fingerprints(f) for f in RECORDED_FAMILIES}, indent=1))
