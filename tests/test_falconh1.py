"""Falcon-H1 (Mamba-2 heads beside attention heads in every block) against the
plain float32 reference the benchmark keeps, ``chipbench/reference/falconh1.py``.

Toy size on the CPU (``builtin:falconh1-test``: 2 blocks, hidden 64, 4 heads /
2 KV heads of 16, 4 mixer heads of 16 in 2 groups, state 32, chunk 8, every
multiplier other than 1), float32 on both sides, so the mathematics has to
agree: the chunked scan against the token-by-token recurrence, the state
through the sampler's cache, left padding with lengths off the chunk grid, the
hydra branch replay, and the scan's gradient. Also here: the KV-only rollout
paths refuse the family by name, and the Mistral, GPT-J, OLMoE and Falcon-H1
toy presets trace to the programs they had before the per-layer attention
layouts (PR 33) were added.
"""

import hashlib
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import falconh1 as reference
from trlx_tpu.models import transformer
from trlx_tpu.models.transformer import (
    CausalTransformer,
    TransformerConfig,
    config_from_spec,
    make_kv_cache,
)
from trlx_tpu.ops.cache_layout import refuse
from trlx_tpu.ops.ssd import ssd_chunked, ssd_step

# Relative L2 of the logits. Both sides compute in float32 and the CPU's
# matmuls are exact float32, so what is left is the order of summation: the
# scan's chunks against one token at a time. Measured 3e-7 to 9e-7; the same
# system in bfloat16 reads 1e-2, the mildest planted fault 3e-2.
TOL = 1e-4

CFG = TransformerConfig.falconh1("test", param_dtype=jnp.float32, dtype=jnp.float32)
DIMS = {
    "num_hidden_layers": CFG.num_layers,
    "num_attention_heads": CFG.num_heads,
    "num_key_value_heads": CFG.kv_heads,
    "head_dim": CFG.dims_per_head,
    "rms_norm_eps": CFG.layer_norm_epsilon,
    "rope_theta": CFG.rope_theta,
    "mamba_n_heads": CFG.mamba_heads,
    "mamba_d_head": CFG.mamba_head_dim,
    "mamba_n_groups": CFG.mamba_groups,
    "mamba_d_state": CFG.mamba_state,
    "mamba_chunk_size": CFG.mamba_chunk,
    **{k: getattr(CFG, k) for k in (
        "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
        "attention_out_multiplier", "key_multiplier", "mlp_multipliers", "ssm_in_multiplier",
        "ssm_out_multiplier", "ssm_multipliers")},
}
MODEL = CausalTransformer(CFG)
B, T = 3, 29  # 29 tokens: three chunks of 8 and five over


def seeded_params(seed):
    """The module's own tree, refilled so that every part matters at this
    size: matrices at 1/sqrt(fan_in), scales and the conv scattered, ``A`` in
    [0.5, 4] and ``dt`` about 0.1, so a state outlives a chunk of 8."""
    shapes = jax.eval_shape(
        lambda: MODEL.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    )
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rs = np.random.RandomState(seed)
    out = []
    for path, leaf in leaves:
        name = getattr(path[-1], "key", "")
        if name in ("scale", "norm_scale", "D"):
            x = 1.0 + 0.2 * rs.randn(*leaf.shape)
        elif name == "embedding":
            x = rs.randn(*leaf.shape)
        elif name == "A_log":
            x = np.log(rs.uniform(0.5, 4.0, leaf.shape))
        elif name == "dt_bias":
            x = rs.uniform(-3.0, -1.5, leaf.shape)
        elif name in ("conv_weight", "conv_bias"):
            x = 0.5 * rs.randn(*leaf.shape)
        else:
            x = rs.randn(*leaf.shape) / np.sqrt(leaf.shape[-2])
        out.append(jnp.asarray(x, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def batch(seed):
    """Left-padded rows: row ``i`` has ``5 * i`` padding tokens, so the real
    lengths 29, 24 and 19 are all off the chunk grid."""
    rs = np.random.RandomState(seed)
    ids = rs.randint(0, CFG.vocab_size, (B, T))
    mask = np.ones((B, T), np.int32)
    for i in range(B):
        mask[i, : 5 * i] = 0
    return jnp.asarray(ids), jnp.asarray(mask)


def rel_l2(got, want, mask):
    m = jnp.asarray(mask, jnp.float32)[..., None]
    return float(jnp.sqrt(jnp.sum(((got - want) * m) ** 2) / jnp.sum((want * m) ** 2)))


def system_logits(params, ids, mask, cfg=CFG):
    return CausalTransformer(cfg).apply({"params": params}, ids, attention_mask=mask)["logits"]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_matches_reference(seed):
    params, (ids, mask) = seeded_params(seed), batch(seed)
    want = reference.logits(params, DIMS, ids, mask, (0, T))
    assert rel_l2(system_logits(params, ids, mask), want, mask) < TOL


@pytest.mark.parametrize("prompt", [13, 16])
def test_prefill_then_decode_matches_reference_full_forward(prompt):
    """The state the scan ends in and the conv's last rows go through the
    cache; each decode step reads and overwrites both."""
    params, (ids, mask) = seeded_params(3), batch(3)
    want = reference.logits(params, DIMS, ids, mask, (0, T))
    cache = make_kv_cache(CFG, B, T)
    assert cache[0]["ssm"].dtype == jnp.float32 and cache[0]["ssm"].shape == (B, 4, 16, 32)
    assert cache[0]["conv"].shape == (B, 3, 4 * 16 + 2 * 2 * 32)
    slots = jnp.concatenate([mask[:, :prompt], jnp.zeros((B, T - prompt), jnp.int32)], axis=1)
    step = jax.jit(lambda ids_, slots_, cache_, at: MODEL.apply(
        {"params": params}, ids_, attention_mask=slots_, cache=cache_, cache_index=at))
    out = step(ids[:, :prompt], slots, cache, jnp.asarray(0))
    assert rel_l2(out["logits"], want[:, :prompt], mask[:, :prompt]) < TOL
    for t in range(prompt, T):
        slots = slots.at[:, t].set(1)
        out = step(ids[:, t : t + 1], slots, out["cache"], jnp.asarray(t))
        assert rel_l2(out["logits"], want[:, t : t + 1], mask[:, t : t + 1]) < TOL, t


def _scan_inputs(seed, t=21):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (2, t, 4, 16))
    dt = jax.nn.softplus(jax.random.normal(k[1], (2, t, 4)) - 2.0)
    A = -jnp.exp(jax.random.normal(k[2], (4,)))
    Bm, Cm = jax.random.normal(k[3], (2, t, 2, 32)), jax.random.normal(k[4], (2, t, 2, 32))
    return x, dt, A, Bm, Cm, jnp.ones((4,))


def _token_by_token(x, dt, A, Bm, Cm, D):
    def token(s, inp):
        y, s = ssd_step(s, *inp[:2], A, *inp[2:], D)
        return s, y

    over_t = lambda a: jnp.moveaxis(a, 1, 0)
    s, y = jax.lax.scan(token, jnp.zeros((2, 4, 16, 32)), tuple(map(over_t, (x, dt, Bm, Cm))))
    return jnp.moveaxis(y, 0, 1), s


@pytest.mark.parametrize("chunk", [8, 21, 128])
def test_chunked_scan_is_the_recurrence_whatever_the_chunk(chunk):
    """Chunk 8 (two whole chunks and five over), chunk T, and one larger than
    T give the token-by-token outputs and final state."""
    x, dt, A, Bm, Cm, D = _scan_inputs(0)
    want_y, want_s = _token_by_token(x, dt, A, Bm, Cm, D)
    y, s = ssd_chunked(x, dt, A, Bm, Cm, D, chunk=chunk)
    assert float(jnp.abs(y - want_y).max()) < 1e-4 and float(jnp.abs(s - want_s).max()) < 1e-4


@pytest.mark.parametrize("wrt", [0, 1, 3, 4])
def test_gradient_through_the_chunked_scan_is_the_recurrences(wrt):
    args = _scan_inputs(1)
    loss = lambda f: lambda *a: jnp.sum(f(*a)[0] ** 2) + jnp.sum(f(*a)[1])
    got = jax.grad(loss(lambda *a: ssd_chunked(*a, chunk=8)), argnums=wrt)(*args)
    want = jax.grad(loss(_token_by_token), argnums=wrt)(*args)
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) < TOL


@pytest.mark.parametrize("scan_layers", [False, True])
def test_hydra_branch_from_block_one_is_the_full_forwards_top(scan_layers):
    params, (ids, mask) = seeded_params(4), batch(4)
    cfg = CFG
    if scan_layers:
        import dataclasses

        cfg = dataclasses.replace(CFG, scan_layers=True)
        params = transformer.stack_layer_params(params, CFG.num_layers)
    model = CausalTransformer(cfg)
    full = model.apply({"params": params}, ids, attention_mask=mask, branch_layer=1)
    top = model.apply({"params": params}, full["branch_input"], 1, mask,
                      method=CausalTransformer.forward_branch)
    assert rel_l2(top["logits"], full["logits"], mask) < 1e-6
    assert rel_l2(full["logits"], reference.logits(
        transformer.unstack_layer_params(params), DIMS, ids, mask, (0, T)), mask) < TOL


def test_loss_gradients_match_the_references():
    """The learner's path: no cache, the chunked scan rematerialised in the
    backward pass, against autodiff through the token-by-token reference."""
    params, (ids, mask) = seeded_params(7), batch(7)

    def loss(logits_of):
        def f(p):
            lp = jax.nn.log_softmax(logits_of(p)[:, :-1])
            picked = jnp.take_along_axis(lp, ids[:, 1:, None], axis=-1)[..., 0]
            real = mask[:, :-1] * mask[:, 1:]  # a real position predicting a real token
            return -jnp.sum(picked * real) / jnp.sum(real)
        return f

    got = jax.grad(loss(lambda p: system_logits(p, ids, mask)))(params)
    want = jax.grad(loss(lambda p: reference.logits(p, DIMS, ids, mask, (0, T))))(params)
    flat_got, flat_want = jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)
    for (path, g), w in zip(flat_got, flat_want):
        err = float(jnp.linalg.norm(g - w) / jnp.maximum(jnp.linalg.norm(w), 1e-12))
        assert err < TOL, (jax.tree_util.keystr(path), err)


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_planted_fault_moves_the_logits(fault):
    params, (ids, mask) = seeded_params(5), batch(5)
    clean = reference.logits(params, DIMS, ids, mask, (0, T))
    moved = rel_l2(reference.logits(params, DIMS, ids, mask, (0, T), fault=fault), clean, mask)
    assert moved > 100 * TOL, (fault, moved)
    assert rel_l2(system_logits(params, ids, mask), clean, mask) < TOL


def test_padding_feeds_nothing_into_the_state():
    """A left-padded row's logits are those of the row alone."""
    params, (ids, mask) = seeded_params(6), batch(6)
    padded = system_logits(params, ids, mask)
    alone = system_logits(params, ids[2:, 10:], mask[2:, 10:])
    assert rel_l2(padded[2:, 10:], alone, mask[2:, 10:]) < 1e-5


@pytest.mark.parametrize("key", ["mlp_multipliers", "ssm_multipliers"])
def test_preset_holds_the_published_list_of_multipliers(key):
    """``TransformerConfig`` keeps plain tuples and a config file spells a
    list, which ``chipbench/job.py::check_published_widths`` cannot hold equal
    at run time (the two keys are not in the configuration's ``maps``)."""
    from chipbench import job

    big = config_from_spec("builtin:falconh1-34b")
    published = job.load_config("falcon-h1-34b-l4")
    assert key not in published["maps"]
    assert type(getattr(big, key)) is tuple and getattr(big, key) == tuple(published["published"][key])
    assert hash(big) == hash(config_from_spec("builtin:falconh1-34b"))
    assert (big.mamba_d_ssm, big.mamba_conv_channels, big.dims_per_head) == (4096, 5120, 128)


# ---------------------------------------------------------------------------
# the KV-only rollout paths refuse the family by name
# ---------------------------------------------------------------------------


def cache_of(cfg):
    return lambda B, S: make_kv_cache(cfg, B, S)


REFUSAL = r"{path} does not support a model whose cache holds recurrent state \(beside K and V, or with its conv's rows a layer's whole cache\) \(leaves \['conv', 'ssm'\]\): .*recurrent state.*B7[bc]\); use the plain sampler"


@pytest.mark.parametrize("path", ["slot_refill", "engine", "prefix_cache", "speculative"])
@pytest.mark.parametrize("scan_layers", [False, True])
def test_kv_only_path_refuses_the_hybrid_cache_by_name(path, scan_layers):
    import dataclasses

    hybrid = dataclasses.replace(CFG, scan_layers=scan_layers)
    with pytest.raises(NotImplementedError, match="^" + REFUSAL.format(path=path)):
        refuse(jax.eval_shape(lambda: make_kv_cache(hybrid, 1, 1)), path, 1)
    kv_only = TransformerConfig.mistral("test", scan_layers=scan_layers)
    refuse(make_kv_cache(kv_only, 1, 1), path, 1)  # passes


def build_slot_refill(paged):
    from trlx_tpu.ops.paged_kv import PagedSpec
    from trlx_tpu.ops.sampling import GenerationConfig
    from trlx_tpu.ops.slot_refill import make_slot_refill_fns

    make_slot_refill_fns(
        None, cache_of(CFG), 2, 4, GenerationConfig(max_new_tokens=2, per_row_rng=True),
        paged=PagedSpec(block_size=2, max_blocks=8) if paged else None)


def build_prefix_cache():
    import types

    from trlx_tpu.engine.core import ContinuousEngine
    from trlx_tpu.ops.paged_kv import PagedKV, PagedSpec

    pool = PagedKV(pool=make_kv_cache(CFG, 8, 2), block_table=jnp.zeros((2, 3), jnp.int32))
    fns = types.SimpleNamespace(  # programs built some other way than make_slot_refill_fns
        init_state=lambda: types.SimpleNamespace(cache=pool), batch_size=2, prompt_len=4,
        max_new_tokens=2, paged=PagedSpec(block_size=2, max_blocks=8), speculative=0)
    ContinuousEngine(fns, None, 0, prewarm=False, prefix_cache=True)


def sample_speculatively():
    from trlx_tpu.ops.sampling import GenerationConfig
    from trlx_tpu.ops.speculative import generate_speculative

    ids = jnp.ones((2, 4), jnp.int32)
    generate_speculative(
        None, None, None, None, cache_of(CFG), cache_of(TransformerConfig.gpt2("test")),
        ids, ids, jax.random.PRNGKey(0), GenerationConfig(max_new_tokens=2))


@pytest.mark.parametrize("build,path", [
    (lambda: build_slot_refill(paged=False), "slot_refill"),
    (lambda: build_slot_refill(paged=True), "engine"),
    (build_prefix_cache, "prefix_cache"),
    (sample_speculatively, "speculative"),
], ids=["slot_refill", "engine", "prefix_cache", "speculative"])
def test_path_stops_where_it_is_built_with_its_sentence(build, path):
    """A direct caller of a KV-only path, not only the trainer's switches."""
    with pytest.raises(NotImplementedError, match="^" + REFUSAL.format(path=path)):
        build()


@pytest.mark.parametrize("switch,path", [
    ({"train": {"continuous_batching": True}}, "slot_refill"),
    ({"train": {"continuous_batching": True}, "engine": {"backend": "paged"}}, "engine"),
    ({"train": {"continuous_batching": True}, "engine": {"backend": "paged", "prefix_cache": True}}, "engine"),
    ({"model": {"draft_model_path": "builtin:gpt2-test"}}, "speculative"),
], ids=["continuous_batching", "engine", "prefix_cache", "speculative"])
def test_trainer_run_stops_at_its_first_rollout_with_the_paths_sentence(switch, path):
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.pipeline.offline_pipeline import PromptPipeline
    from trlx_tpu.trainer.ppo import PPOTrainer

    switch = {k: dict(v) for k, v in switch.items()}
    switch.setdefault("model", {})["model_path"] = "builtin:falconh1-test"
    cfg = default_ppo_config().evolve(
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        train=dict(tracker=None, **switch.pop("train", {})), **switch)
    trainer = PPOTrainer(cfg, reward_fn=lambda samples, **kw: [0.0] * len(samples))
    trainer.add_prompt_pipeline(PromptPipeline(["hello world"] * cfg.method.chunk_size, 8, trainer.tokenizer))
    with pytest.raises(NotImplementedError, match="^" + REFUSAL.format(path=path)):
        trainer.make_experience(cfg.method.chunk_size)


@pytest.mark.parametrize("family,state_bytes", [
    # 2 blocks x 3 rows x (4 x 16 x 32 float32 + 3 x 192 float32 conv rows)
    ("falconh1", 2 * 3 * (4 * 16 * 32 + 3 * 192) * 4),
    ("mistral", 0),
])
def test_collection_counters_read_the_cache_by_leaf_name(family, state_bytes):
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.ops.sampling import GenerationConfig
    from trlx_tpu.trainer.ppo import PPOTrainer

    cfg = default_ppo_config().evolve(
        tokenizer=dict(tokenizer_path="builtin:bytes"), train=dict(tracker=None),
        model=dict(model_path=f"builtin:{family}-test"),
        parallel=dict(param_dtype="float32", compute_dtype="float32"))
    trainer = PPOTrainer(cfg, reward_fn=lambda samples, **kw: [0.0] * len(samples))
    trainer._note_dense_kv_gauge((3, 8), GenerationConfig(max_new_tokens=4))
    # k, v; 2 blocks; 12 slots, or the 8 of the Mistral toy's window: a window layer's ring
    slots = min(12, trainer.tcfg.sliding_window or 12)
    kv = 2 * 2 * 3 * slots * trainer.tcfg.kv_heads * trainer.tcfg.dims_per_head * 4
    assert trainer.last_cache_stats == {
        "rollout/kv_cache_bytes": float(kv), "rollout/ssm_state_bytes": float(state_bytes), "rollout/kv_lane_heads": 1.0}


def test_hf_interop_says_there_is_no_converter():
    import types

    from trlx_tpu.models.hf_interop import config_from_hf

    with pytest.raises(ValueError, match="falcon_h1.*no HF checkpoint conversion"):
        config_from_hf(types.SimpleNamespace(model_type="falcon_h1"))


# ---------------------------------------------------------------------------
# dense and MoE presets trace to the programs they had before this family
# ---------------------------------------------------------------------------

RECORDED = os.path.join(os.path.dirname(__file__), "fixtures", "programs_before_smallthinker.json")
RECORDED_FAMILIES = ("mistral", "gptj", "olmoe", "falconh1")


def program_fingerprints(family):
    """sha256 of the toy preset's parameter tree, cache tree, and the jaxpr
    text of one forward and one decode step (float32, xla attention). Eight
    slots: no more than the Mistral toy's window, so that no layer's cache is
    shorter than the row (a window layer's is a ring of ``min(S, window)``
    slots since the per-layer layouts)."""
    cfg = config_from_spec(f"builtin:{family}-test", attention_impl="xla")
    model = CausalTransformer(cfg)
    ids = jnp.zeros((2, 6), jnp.int32)
    mask = jnp.ones((2, 6), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids)["params"])
    cache = jax.eval_shape(lambda: make_kv_cache(cfg, 2, 8))
    slots = jnp.ones((2, 8), jnp.int32)
    texts = {
        "params": str(jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), params)),
        "cache": str(jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), cache)),
        "forward": str(jax.make_jaxpr(
            lambda p: model.apply({"params": p}, ids, attention_mask=mask, branch_layer=1))(params)),
        "decode": str(jax.make_jaxpr(
            lambda p, c: model.apply({"params": p}, ids[:, :1], attention_mask=slots, cache=c,
                                     cache_index=jnp.asarray(6, jnp.int32)))(params, cache)),
    }
    clean = lambda text: re.sub(r"0x[0-9a-f]+", "0x", text)
    return {k: hashlib.sha256(clean(v).encode()).hexdigest() for k, v in texts.items()}


@pytest.mark.parametrize("family", RECORDED_FAMILIES)
def test_kv_only_presets_trace_to_the_programs_recorded_before_the_family(family, clean_trace_state):
    """Recorded on the commit before the per-layer attention layouts (PR 33's
    parent) by this function (``python tests/test_falconh1.py`` there writes
    the file): parameter tree, cache tree and both jaxprs byte for byte, of
    the three KV-only presets and of this family's own. ``olmoe``'s two jaxprs
    were recorded again in PR 40: ``MoEMLP._dropless_rows`` passes the sorted
    rows through a select that lets the gradient of the rows in a group
    alone through (``ragged_dot``'s backward left the others' unwritten on the
    chip), one ``select_n`` and one ``stop_gradient`` a layer, which the
    compiler folds out of the forward, and once more in PR 44: the layer's two
    row permutations go through ``permute_rows``, so each gather stands inside
    a ``custom_vjp_call`` and ``unsort`` is traced before the first of them
    (compiled for the CPU both toy programs are the same instructions as on
    PR 44's parent, names aside); trees and every other family's programs are
    the first recording's."""
    # other test files of the same worker set jax_default_matmul_precision at
    # import, and a precision is printed on every dot of a jaxpr
    with jax.default_matmul_precision(None), open(RECORDED) as f:
        assert program_fingerprints(family) == json.load(f)[family]


if __name__ == "__main__":  # the recorder
    print(json.dumps({f: program_fingerprints(f) for f in RECORDED_FAMILIES}, indent=1))
