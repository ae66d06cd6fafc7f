"""Set-up runs a handful of programs, not one an operation (PR 36).

``build_causal_lm`` / ``build_seq2seq_lm`` build the parameters in one jitted
program born under ``param_shardings``; the optimizer's state, the step
counter and both rng streams are a second; PPO's reference snapshot a third;
and ``generate()`` walks the cache's shapes once a shape. An eager
``module.init`` (a program an operation, each compiled at every start) must
not come back unnoticed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.data.configs import ModelConfig, ParallelConfig
from trlx_tpu.data.default_configs import default_grpo_config, default_ppo_config
from trlx_tpu.models.builder import build_causal_lm, build_seq2seq_lm
from trlx_tpu.models.heads import sync_target_q_params
from trlx_tpu.observability import tracing
from trlx_tpu.parallel.mesh import make_mesh, set_global_mesh
from trlx_tpu.parallel.sharding import param_shardings

# one toy configuration of each family the benchmark's cells use, and each
# head; bf16 where the cells keep bf16 parameters
BUILDS = {
    "dense_gqa": ("builtin:mistral-test", "causal", None, "bfloat16"),
    "gptj_parallel_residual": ("builtin:gptj-test", "causal", "value", "bfloat16"),
    "dropless_moe": ("builtin:olmoe-test", "causal", None, "bfloat16"),
    "falconh1_hybrid": ("builtin:falconh1-test", "causal", "value", "bfloat16"),
    "smallthinker_mixed_layout": ("builtin:smallthinker-test", "causal", None, "bfloat16"),
    "pangu_latent_attention": ("builtin:pangu-test", "causal", "value", "bfloat16"),
    "value_head_f32": ("builtin:gpt2-test", "causal", "value", "float32"),
    "ilql_heads_f32": ("builtin:gpt2-test", "causal", "ilql", "float32"),
    "seq2seq_value_f32": ("builtin:t5-test", "seq2seq", "value", "float32"),
    "seq2seq_ilql_bf16": ("builtin:t5-test", "seq2seq", "ilql", "bfloat16"),
}


def _ulps(a, b):
    """Distance in units in the last place between two bf16 or float32 arrays."""
    bits, sign = (np.uint16, 0x8000) if a.dtype == jnp.bfloat16 else (np.uint32, 0x80000000)

    def ordered(x):  # sign-magnitude bits to a scale on which neighbours differ by 1
        raw = np.asarray(x).view(bits).astype(np.int64)
        return np.where(raw & sign, -(raw & (sign - 1)), raw)

    return np.abs(ordered(a) - ordered(b))


@pytest.mark.parametrize("name", sorted(BUILDS))
def test_jitted_build_equals_an_eager_init(name):
    path, arch, head, dtype = BUILDS[name]
    parallel = ParallelConfig(data=2, fsdp=2, model=2, param_dtype=dtype, compute_dtype="float32")
    mesh = make_mesh(parallel)
    set_global_mesh(mesh)
    build = build_seq2seq_lm if arch == "seq2seq" else build_causal_lm
    module, params, _ = build(ModelConfig(model_path=path), parallel, head=head, seed=7, mesh=mesh)

    # the eager init written out: what the builder did before it was one program
    rng = jax.random.PRNGKey(7)
    if arch == "seq2seq":
        eager = module.init(
            rng, jnp.zeros((1, 8), jnp.int32), decoder_input_ids=jnp.zeros((1, 4), jnp.int32)
        )["params"]
    else:
        eager = module.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
    if head == "ilql":
        eager = sync_target_q_params(eager, alpha=1.0)

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    flat_eager, treedef_eager = jax.tree_util.tree_flatten_with_path(eager)
    assert treedef == treedef_eager
    shardings = jax.tree_util.tree_leaves(param_shardings(params, mesh))
    assert len(flat) > 10
    for (key, leaf), (_, want), sharding in zip(flat, flat_eager, shardings):
        where = jax.tree_util.keystr(key)
        assert leaf.dtype == want.dtype == jnp.dtype(dtype), where
        assert leaf.shape == want.shape, where
        assert leaf.sharding.is_equivalent_to(sharding, leaf.ndim), where
        # the same random bits, and then one unit in the last place at most:
        # inside one program XLA folds `sqrt(2) * erf_inv(u) * std` into one
        # multiplication by `sqrt(2) * std` (and may keep a bf16 product in
        # float32), where the eager walk rounded after each
        assert _ulps(leaf, want).max() <= 1, where
        if key[-1].key not in ("kernel", "embedding") and leaf.ndim < 2:
            # biases, norm scales, the mixer's constants: no scaled draw, no fold
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(want), err_msg=where)

    # abstract=True is what it was: the same tree of shapes, nothing placed
    _, shapes, _ = build(ModelConfig(model_path=path), parallel, head=head, seed=7, abstract=True)
    assert jax.tree_util.tree_structure(shapes) == treedef
    assert all(isinstance(s, jax.ShapeDtypeStruct) for s in jax.tree_util.tree_leaves(shapes))


def test_one_init_program_serves_every_seed(monkeypatch):
    """The benchmark draws a new seed for every run: a seed baked into the
    program as a constant would be another program, and a compile, a start."""
    import trlx_tpu.models.builder as builder

    texts = []

    def lowered_only(make_params, seed, mesh, abstract, load_backbone=None, stored=None):
        texts.append(jax.jit(make_params).lower(np.int64(seed)).as_text())
        return jax.eval_shape(make_params, np.int64(seed))

    monkeypatch.setattr(builder, "_build_params", lowered_only)
    for seed in (7, 3_000_000_019):  # the driver's seeds pass 2**31
        build_causal_lm(ModelConfig(model_path="builtin:gpt2-test"), head="value", seed=seed)
    assert texts[0] == texts[1]
    # and the key the program makes of it is the one a Python int gives
    key = jax.jit(jax.random.PRNGKey)(np.int64(3_000_000_019))
    np.testing.assert_array_equal(np.asarray(key), np.asarray(jax.random.PRNGKey(3_000_000_019)))


def _toy_config(kind):
    base = default_grpo_config if kind == "grpo" else default_ppo_config
    return base().evolve(
        train=dict(seq_length=24, batch_size=8, total_steps=4, tracker=None,
                   checkpoint_dir="/nonexistent"),
        model=dict(model_path="builtin:gpt2-test", num_layers_unfrozen=1 if kind == "ppo" else -1),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        method=dict(
            num_rollouts=16, chunk_size=8, ppo_epochs=1,
            gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0, do_sample=True),
            **(dict(group_size=4) if kind == "grpo" else {}),
        ),
    )


def _build_trainer(kind):
    import trlx_tpu.trainer.grpo  # noqa: F401  (registration)
    import trlx_tpu.trainer.ppo  # noqa: F401
    from trlx_tpu.trainer import get_trainer

    config = _toy_config(kind)
    return get_trainer(config.train.trainer)(config=config, reward_fn=lambda **kw: [0.0])


# make_params, init_state, ref_snapshot, and room for one more: the eager
# init was 107 to 124 programs at this size
MAX_BUILD_PROGRAMS = 4


@pytest.mark.parametrize("kind", ["ppo", "grpo"])
def test_building_a_trainer_compiles_a_handful_of_programs(kind):
    tracing.install_sources()
    before, names = tracing.mark(), tracing.programs()
    trainer = _build_trainer(kind)
    built = tracing.since(before, tracing.mark())
    table = tracing.programs_table(names, rows=40)
    assert 1 <= built["runtime/programs"] <= MAX_BUILD_PROGRAMS, table
    compiled = {fun for fun, row in tracing.programs().items()
                if row.get("programs", 0) > names.get(fun, {}).get("programs", 0)}
    assert compiled <= {"make_params", "init_state", "ref_snapshot"}, table
    # the counters and streams the program returned are what the eager code made
    rollout_rng, state_rng = jax.random.split(jax.random.PRNGKey(trainer.config.train.seed))
    np.testing.assert_array_equal(np.asarray(trainer.state.rng), np.asarray(state_rng))
    np.testing.assert_array_equal(np.asarray(trainer._rollout_rng), np.asarray(rollout_rng))
    assert int(trainer.state.step) == 0 and trainer.state.step.dtype == jnp.int32


def test_generate_walks_the_cache_shapes_once_a_shape():
    tracing.install_sources()
    trainer = _build_trainer("grpo")
    ids = np.full((8, 6), 65, np.int32)

    def calls_that_traced(input_ids):
        def traced():
            return tracing.programs().get("kv_cache_shapes", {}).get("runtime/trace", 0.0)

        before = traced()
        trainer.generate(input_ids, np.ones_like(input_ids))
        return traced() > before

    assert calls_that_traced(ids)  # the shape's first call walks the pytree
    assert not calls_that_traced(ids)  # its second reads the memo
    gauges = dict(trainer.last_cache_stats), trainer.last_kv_layers
    assert not calls_that_traced(ids)
    assert (dict(trainer.last_cache_stats), trainer.last_kv_layers) == gauges
    assert calls_that_traced(np.full((8, 9), 65, np.int32))  # another shape, its own walk
    assert trainer.last_cache_stats["rollout/kv_cache_bytes"] > gauges[0]["rollout/kv_cache_bytes"]
    assert not calls_that_traced(ids)  # and the first is still remembered
    assert len(trainer._kv_cache_shapes) == 2


@pytest.mark.parametrize("nlu", [1, -1])
def test_ref_snapshot_owns_its_buffers(nlu):
    """The train step donates its input state: a reference leaf that aliased
    a parameter would be freed under the scoring forward."""
    import trlx_tpu.trainer.ppo  # noqa: F401
    from trlx_tpu.trainer import get_trainer

    config = _toy_config("ppo").evolve(
        model=dict(num_layers_unfrozen=nlu), parallel=dict(data=2, fsdp=2, model=2))
    trainer = get_trainer(config.train.trainer)(config=config, reward_fn=lambda **kw: [0.0])
    backbone = trainer.state.params["backbone"]
    ref = jax.tree_util.tree_leaves_with_path(trainer.ref_params)
    assert len(ref) >= 5

    def pointers(x):
        return {s.data.unsafe_buffer_pointer() for s in x.addressable_shards}

    owned = set().union(*(pointers(x) for x in jax.tree_util.tree_leaves(trainer.state.params)))
    for key, leaf in ref:
        source = backbone
        for k in key:
            source = source[k.key]
        where = jax.tree_util.keystr(key)
        assert not pointers(leaf) & owned, where
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(source), err_msg=where)
        # the copy lies as the parameter it was taken from does
        assert leaf.sharding.is_equivalent_to(source.sharding, leaf.ndim), where
    # deleting the state's buffers (what donation does) leaves the snapshot whole
    for x in jax.tree_util.tree_leaves(trainer.state.params):
        x.delete()
    assert all(np.isfinite(np.asarray(leaf, np.float32)).all() for _, leaf in ref)
