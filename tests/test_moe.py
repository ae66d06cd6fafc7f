"""Mixture-of-experts MLP + expert parallelism.

Beyond-reference capability (SURVEY.md §2.3 lists EP as n/a in the
reference): mixtral-family MoE backbones with GShard-style einsum dispatch
over the mesh's ``expert`` axis (``trlx_tpu/models/transformer.py::MoEMLP``,
``trlx_tpu/parallel/mesh.py``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.models.transformer import (
    CausalTransformer,
    MoEMLP,
    TransformerConfig,
    router_aux_summary,
    stack_layer_params,
)


def _cfg(**overrides):
    overrides.setdefault("dtype", jnp.float32)
    overrides.setdefault("param_dtype", jnp.float32)
    return TransformerConfig.mixtral("test", **overrides)


def _moe_apply(cfg, x, seed=0):
    m = MoEMLP(cfg)
    params = m.init(jax.random.PRNGKey(seed), x)["params"]
    return params, m.apply({"params": params}, x)


def test_one_expert_equals_dense_math():
    """E=1, K=1, ample capacity: the MoE layer IS its single expert — output
    must equal the gated-MLP math applied to every token (gate prob is
    softmax over one logit ≡ 1)."""
    cfg = _cfg(num_experts=1, num_experts_per_tok=1, moe_capacity_factor=2.0)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, cfg.hidden_size), jnp.float32)
    params, (y, aux) = _moe_apply(cfg, x)
    w_gate, w_up, w_down = params["w_gate"][0], params["w_up"][0], params["w_down"][0]
    expected = (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down
    np.testing.assert_allclose(np.asarray(y), np.asarray(expected), rtol=1e-5, atol=1e-5)
    # single expert: assignments and probs both uniform-of-one → balance = 1
    np.testing.assert_allclose(float(router_aux_summary(aux)[0]), 1.0, rtol=1e-6)


def test_topk_gates_renormalized_and_combine_conserves_mass():
    """With ample capacity every token is dispatched with weights that sum to
    1: feeding x=const through identity-ish experts must reproduce the gate
    mass. Checked via dispatch of ones: sum over (E, C) of combine == 1."""
    cfg = _cfg(num_experts=4, num_experts_per_tok=2, moe_capacity_factor=4.0)
    x = jnp.asarray(np.random.RandomState(1).randn(3, 8, cfg.hidden_size), jnp.float32)

    # reach into the module: replicate its routing to get combine weights
    m = MoEMLP(cfg)
    params = m.init(jax.random.PRNGKey(0), x)["params"]
    logits = x.astype(jnp.float32) @ params["router"]["kernel"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, _ = jax.lax.top_k(probs, 2)
    gate_vals = gate_vals / gate_vals.sum(-1, keepdims=True)
    np.testing.assert_allclose(
        np.asarray(gate_vals.sum(-1)), np.ones((3, 8)), rtol=1e-6
    )

    # behavioral check of the same invariant: scaling every expert to the
    # identity map makes y == x exactly when no token is dropped
    eye_like = {
        "router": params["router"],
        "w_gate": jnp.zeros_like(params["w_gate"]),  # silu(0)=0 → gate path off
        "w_up": params["w_up"],
        "w_down": params["w_down"],
    }
    y, _ = m.apply({"params": eye_like}, x)
    assert np.all(np.isfinite(np.asarray(y)))


def test_uniform_router_aux_is_one():
    """Zero router weights → uniform probs; with assignments then (near)
    uniform over experts by the top-k tie-break, the Switch balance loss is
    E·Σ f·p = Σ f = 1 exactly (p_e = 1/E regardless of f)."""
    cfg = _cfg(num_experts=4, num_experts_per_tok=2)
    x = jnp.asarray(np.random.RandomState(2).randn(2, 16, cfg.hidden_size), jnp.float32)
    m = MoEMLP(cfg)
    params = m.init(jax.random.PRNGKey(0), x)["params"]
    params = dict(params, router={"kernel": jnp.zeros_like(params["router"]["kernel"])})
    _, aux = m.apply({"params": params}, x)
    lb, z = np.asarray(router_aux_summary(aux))
    np.testing.assert_allclose(float(lb), 1.0, rtol=1e-6)
    assert float(z) > 0.0  # z-loss = mean lse² > 0 even at uniform


def test_capacity_overflow_drops_to_residual():
    """A capacity of 1 slot per expert forces drops; dropped tokens must get
    *zero* expert output (the Block's residual then passes them through) and
    nothing may go non-finite."""
    cfg = _cfg(num_experts=2, num_experts_per_tok=1, moe_capacity_factor=1e-9)
    x = jnp.asarray(np.random.RandomState(3).randn(1, 12, cfg.hidden_size), jnp.float32)
    _, (y, _) = _moe_apply(cfg, x)
    y = np.asarray(y)
    assert np.all(np.isfinite(y))
    # C = 1 and 12 tokens over 2 experts → at most 2 rows can be non-zero
    nonzero_rows = np.any(np.abs(y[0]) > 0, axis=-1).sum()
    assert nonzero_rows <= 2, nonzero_rows


def test_padding_tokens_do_not_route_or_train_router():
    """Masked (padding) tokens claim no expert capacity, leave the layer
    with zero output, and contribute nothing to the router statistics: a
    padded run must match the unpadded prefix run on both outputs and aux."""
    cfg = _cfg(num_experts=4, num_experts_per_tok=2, moe_capacity_factor=8.0)
    d = cfg.hidden_size
    rs = np.random.RandomState(0)
    x_real = jnp.asarray(rs.randn(2, 5, d), jnp.float32)
    pad = jnp.asarray(rs.randn(2, 3, d), jnp.float32)  # garbage pad content
    x_padded = jnp.concatenate([x_real, pad], axis=1)
    mask = jnp.concatenate([jnp.ones((2, 5)), jnp.zeros((2, 3))], axis=1)

    m = MoEMLP(cfg)
    params = m.init(jax.random.PRNGKey(0), x_real)["params"]
    y_prefix, aux_prefix = m.apply({"params": params}, x_real)
    y_padded, aux_padded = m.apply({"params": params}, x_padded, mask)

    np.testing.assert_allclose(
        np.asarray(y_padded[:, :5]), np.asarray(y_prefix), rtol=1e-5, atol=1e-6
    )
    assert np.all(np.asarray(y_padded[:, 5:]) == 0.0)
    np.testing.assert_allclose(
        np.asarray(aux_padded), np.asarray(aux_prefix), rtol=1e-5
    )


def test_group_size_invariant_with_ample_capacity():
    """Dispatch grouping only bounds the slot tensors: with capacity ample
    enough that nothing drops, the output is independent of the group size
    (routing decisions and combine weights are per-token)."""
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(2, 16, 64), jnp.float32)
    cfg_whole = _cfg(num_experts=4, moe_capacity_factor=8.0)
    cfg_grouped = _cfg(num_experts=4, moe_capacity_factor=8.0, moe_group_size=4)
    m = MoEMLP(cfg_whole)
    params = m.init(jax.random.PRNGKey(0), x)["params"]
    y_whole, aux_whole = m.apply({"params": params}, x)
    y_grouped, aux_grouped = MoEMLP(cfg_grouped).apply({"params": params}, x)
    np.testing.assert_allclose(
        np.asarray(y_grouped), np.asarray(y_whole), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(np.asarray(aux_grouped), np.asarray(aux_whole), rtol=1e-6)
    # a non-divisor group size falls back to the largest divisor (static)
    y_odd, _ = MoEMLP(_cfg(num_experts=4, moe_capacity_factor=8.0, moe_group_size=5)).apply(
        {"params": params}, x
    )
    np.testing.assert_allclose(np.asarray(y_odd), np.asarray(y_whole), rtol=1e-5, atol=1e-6)


def test_moe_transformer_forward_scan_and_branch_parity():
    cfg = _cfg()
    m = CausalTransformer(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 259, (2, 16)), jnp.int32)
    params = m.init(jax.random.PRNGKey(0), ids)["params"]
    out = m.apply({"params": params}, ids)
    assert np.all(np.isfinite(np.asarray(out["logits"])))
    assert out["router_aux_loss"].shape == (2,)

    # scan_layers runs the same math over stacked params
    ms = CausalTransformer(_cfg(scan_layers=True))
    outs = ms.apply({"params": stack_layer_params(params, cfg.num_layers)}, ids)
    np.testing.assert_allclose(
        np.asarray(outs["logits"]), np.asarray(out["logits"]), atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(outs["router_aux_loss"]),
        np.asarray(out["router_aux_loss"]),
        rtol=1e-5,
    )

    # hydra branch replay bit-matches the main forward's top layers
    outb = m.apply({"params": params}, ids, branch_layer=1)
    ref = m.apply(
        {"params": params},
        outb["branch_input"],
        1,
        None,
        None,
        None,
        method=CausalTransformer.forward_branch,
    )
    np.testing.assert_allclose(
        np.asarray(ref["logits"]), np.asarray(out["logits"]), atol=1e-5
    )


def test_moe_generate_decode():
    """KV-cache decode through MoE blocks: T=1 groups never drop tokens and
    the sampler runs unchanged."""
    from trlx_tpu.models.builder import build_causal_lm
    from trlx_tpu.models.transformer import make_kv_cache

    from trlx_tpu.data.configs import ModelConfig, ParallelConfig
    from trlx_tpu.ops.sampling import GenerationConfig, generate

    module, params, tcfg = build_causal_lm(
        ModelConfig(
            model_path="builtin:mixtral-test",
            model_extra_kwargs=dict(dtype=jnp.float32, param_dtype=jnp.float32),
        ),
        ParallelConfig(data=1, param_dtype="float32"),
        head="value",
    )
    B, P = 2, 8
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 259, (B, P)), jnp.int32)
    mask = jnp.ones((B, P), jnp.int32)

    def apply_fn(p, input_ids, attention_mask, positions, cache, cache_index, **kw):
        return module.apply(
            {"params": p},
            input_ids,
            attention_mask=attention_mask,
            positions=positions,
            cache=cache,
            cache_index=cache_index,
            **kw,
        )

    out = generate(
        apply_fn,
        params,
        lambda b, s: make_kv_cache(tcfg, b, s),
        ids,
        mask,
        jax.random.PRNGKey(0),
        GenerationConfig(max_new_tokens=6, do_sample=True, eos_token_id=None, pad_token_id=0),
    )
    toks = np.asarray(out.response_tokens)
    assert toks.shape == (B, 6)
    assert np.all((toks >= 0) & (toks < 259))
    assert np.all(np.asarray(out.response_mask) == 1)


def test_moe_expert_parallel_training_step():
    """8-device mesh with a real expert axis (expert=2 × fsdp=2 × data=2):
    params shard over `expert`, one jitted loss+grad step runs, grads are
    finite, and the expert kernels' gradient sharding matches the params."""
    from jax.sharding import PartitionSpec as P

    from trlx_tpu.data.configs import ParallelConfig
    from trlx_tpu.parallel import make_mesh, set_global_mesh
    from trlx_tpu.parallel.sharding import param_specs, shard_params

    cfg = _cfg(num_experts=2)
    mesh = make_mesh(ParallelConfig(data=2, fsdp=2, expert=2))
    set_global_mesh(mesh)
    try:
        m = CausalTransformer(cfg)
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 259, (4, 16)), jnp.int32)
        params = m.init(jax.random.PRNGKey(0), ids)["params"]
        specs = param_specs(params, mesh)
        assert tuple(specs["h_0"]["mlp"]["w_gate"]) == ("expert", "fsdp", "model")
        assert tuple(specs["h_0"]["mlp"]["w_down"]) == ("expert", "model", "fsdp")
        params = shard_params(params, mesh)
        ew = params["h_0"]["mlp"]["w_up"]
        assert ew.sharding.spec == P("expert", "fsdp", "model")

        def loss(p, ids):
            out = m.apply({"params": p}, ids)
            lp = jax.nn.log_softmax(out["logits"][:, :-1].astype(jnp.float32))
            nll = -jnp.take_along_axis(lp, ids[:, 1:, None], axis=-1).mean()
            return nll + 0.01 * out["router_aux_loss"][0]

        with mesh:
            l, g = jax.jit(jax.value_and_grad(loss))(params, ids)
        assert np.isfinite(float(l))
        gleaf = g["h_0"]["mlp"]["w_up"]
        assert np.all(np.isfinite(np.asarray(gleaf)))
        # expert grads flow (routing selects every expert somewhere at E=2)
        assert float(jnp.abs(gleaf).max()) > 0
    finally:
        set_global_mesh(None)


def test_moe_sft_e2e_loss_decreases(tmp_path):
    """A tiny mixtral SFT run through the real trainer: the router aux terms
    ride the loss (stats carry them) and the total loss decreases."""
    from trlx_tpu.data.default_configs import default_sft_config
    from trlx_tpu.trainer import get_trainer
    import trlx_tpu.trainer.sft  # noqa: F401
    import trlx_tpu.pipeline.offline_pipeline  # noqa: F401

    config = default_sft_config().evolve(
        train=dict(
            seq_length=32,
            batch_size=4,
            total_steps=8,
            epochs=100,
            eval_interval=10**6,
            checkpoint_interval=10**6,
            save_best=False,
            tracker=None,
            checkpoint_dir=str(tmp_path / "ckpt"),
        ),
        model=dict(
            model_path="builtin:mixtral-test",
            model_extra_kwargs=dict(router_aux_coef=0.01),
        ),
    )
    trainer = get_trainer(config.train.trainer)(
        config=config, reward_fn=None, metric_fn=None, stop_sequences=[]
    )
    rs = np.random.RandomState(0)
    corpus = ["".join(chr(97 + c) for c in rs.randint(0, 4, 48)) for _ in range(16)]
    trainer.make_experience(corpus, 32)
    trainer.prepare_learning()
    losses = []
    import itertools

    loader = itertools.cycle(list(trainer.train_dataloader))
    for _ in range(8):
        stats = trainer.train_step(next(loader))
        losses.append(float(np.asarray(stats["losses/loss"])))
        assert "losses/router_load_balance" in stats
        lb = float(np.asarray(stats["losses/router_load_balance"]))
        assert np.isfinite(lb) and lb > 0
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def test_mixtral_8x7b_config_partitions():
    """Scale honesty for the MoE family (the dense analogue of the 6B/20B
    tests in tests/test_scan.py): the real mixtral-8x7b preset (~47B params)
    shape-initializes under scan_layers and its stacked expert kernels
    partition over an 8-device fsdp×model×expert mesh — no weights
    materialized."""
    from trlx_tpu.data.configs import ParallelConfig
    from trlx_tpu.models.heads import CausalLMWithValueHead
    from trlx_tpu.parallel.mesh import make_mesh
    from trlx_tpu.parallel.sharding import param_specs

    cfg = TransformerConfig.mixtral("8x7b", scan_layers=True)
    module = CausalLMWithValueHead(cfg)
    shapes = jax.eval_shape(
        lambda rng: module.init(rng, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )
    total = sum(np.prod(l.shape) for l in jax.tree_util.tree_leaves(shapes))
    assert total > 45e9  # mixtral-8x7b really is ~47B params

    mesh = make_mesh(ParallelConfig(data=1, fsdp=2, model=2, expert=2))
    specs = param_specs(shapes, mesh)

    def sharded_size(leaf, spec):
        denom = 1
        for axis in tuple(spec):
            if axis is not None:
                denom *= int(
                    np.prod([mesh.shape[a] for a in (axis if isinstance(axis, tuple) else (axis,))])
                )
        return np.prod(leaf.shape) / denom

    per_device = sum(
        sharded_size(l, s)
        for (_, l), (_, s) in zip(
            jax.tree_util.tree_leaves_with_path(shapes),
            jax.tree_util.tree_leaves_with_path(
                specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
            ),
        )
    )
    # expert kernels are ~27/28 of all params; they must shard 8-way
    assert per_device < total / 6, f"per-device {per_device:.2e} vs total {total:.2e}"
    w = specs["backbone"]["h_scan"]["block"]["mlp"]["w_gate"]
    assert tuple(w) == ("pipe", "expert", "fsdp", "model")


@pytest.mark.slow
def test_moe_through_pipeline_parity():
    """MoE blocks through the GPipe schedule (pipe=2): logits and the router
    aux vector match the unpipelined scan execution."""
    from trlx_tpu.data.configs import ParallelConfig
    from trlx_tpu.parallel import make_mesh, set_global_mesh

    cfg = _cfg(scan_layers=True, attention_impl="xla")
    m = CausalTransformer(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 259, (4, 16)), jnp.int32)
    params = m.init(jax.random.PRNGKey(0), ids)["params"]
    base = m.apply({"params": params}, ids)

    mesh = make_mesh(ParallelConfig(data=2, pipe=2, fsdp=2))
    set_global_mesh(mesh)
    try:
        with mesh:
            piped = jax.jit(lambda p, i: m.apply({"params": p}, i))(params, ids)
        np.testing.assert_allclose(
            np.asarray(piped["logits"]), np.asarray(base["logits"]), atol=2e-4
        )
        # the balance loss is a product of means (E·Σ f̄·p̄): per-microbatch
        # then averaged (pipeline / grad-accum semantics) differs from the
        # full-batch value by O(inter-microbatch routing variance) — close,
        # not equal. The z-loss is a plain token mean and matches tightly.
        np.testing.assert_allclose(
            np.asarray(piped["router_aux_loss"]),
            np.asarray(base["router_aux_loss"]),
            rtol=5e-2,
        )
        np.testing.assert_allclose(
            float(piped["router_aux_loss"][1]),
            float(base["router_aux_loss"][1]),
            rtol=2e-4,
        )
    finally:
        set_global_mesh(None)


# ---------------------------------------------------------------------------
# dropless routing (moe_capacity_factor = 0)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("what", ["forward", "gradients"])
def test_dropless_equals_ample_capacity(what):
    """With capacity for every assignment (factor = E) the one-hot dispatch
    drops nothing, so the two dispatches are the same function: logits and
    router statistics, and the gradients of a loss that uses both."""
    import dataclasses

    ample = _cfg(moe_capacity_factor=4.0)
    dropless = dataclasses.replace(ample, moe_capacity_factor=0.0)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 259, (3, 16)), jnp.int32)
    mask = jnp.ones((3, 16), jnp.int32).at[1, :6].set(0)
    params = CausalTransformer(ample).init(jax.random.PRNGKey(0), ids)["params"]

    def run(cfg, p):
        return CausalTransformer(cfg).apply({"params": p}, ids, attention_mask=mask)

    def loss(cfg, p):
        out = run(cfg, p)
        return jnp.mean(out["logits"] ** 2) + jnp.sum(out["router_aux_loss"])

    if what == "forward":
        a, d = run(ample, params), run(dropless, params)
        for key in ("logits", "router_aux_loss", "router_load"):
            np.testing.assert_allclose(np.asarray(d[key]), np.asarray(a[key]), rtol=1e-5, atol=1e-6)
        assert float(d["router_load"][0]) == 0.0
        return
    ga = jax.grad(lambda p: loss(ample, p))(params)
    gd = jax.grad(lambda p: loss(dropless, p))(params)
    for (path, a), d in zip(jax.tree_util.tree_flatten_with_path(ga)[0], jax.tree_util.tree_leaves(gd)):
        scale = float(jnp.abs(a).max())
        np.testing.assert_allclose(np.asarray(d), np.asarray(a), rtol=1e-4, atol=1e-5 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def _plain_permute_rows(rows, perm, inv):
    """The two permutations of ``MoEMLP._dropless_rows`` as they were written
    before they had a backward of their own: a gather that JAX transposes
    into a scatter-add. The independent statement of what ``permute_rows``
    computes, forward and backward."""
    return rows.at[perm].get(unique_indices=True)


_DROPLESS_LAYERS = {
    # all eight experts held: every assignment is computed
    "all_held": dict(),
    # a chip's share: experts 4 to 6 of 8, so most sorted rows lie past the
    # last group and the select's stop_gradient is on their path
    "held_share": dict(moe_experts_held=3, moe_first_expert=4),
    # padding tokens sort past the last expert with the other chips' rows
    "padding": dict(),
    "padding_held_share": dict(moe_experts_held=2, moe_first_expert=1),
    # the permutations' residuals and backward under rematerialisation
    "checkpoint": dict(moe_experts_held=3, moe_first_expert=4),
    # a layer past MOE_MAX_TOKENS: three pieces of twelve tokens under lax.map
    "pieces": dict(moe_experts_held=3, moe_first_expert=4),
    # ... and with every expert held
    "pieces_all_held": dict(),
}


_DROPLESS_K = 3


def _dropless_layer_loss(case, dtype, permute, monkeypatch, count_calls=True, K=_DROPLESS_K):
    """``(loss(params, x), params, x)`` of a dropless ``MoEMLP`` that moves
    its rows with ``permute`` (None: the layer's own ``permute_rows``).
    ``count_calls=False`` leaves the two call counters at the end of a held
    share's ``aux`` out of the loss: they say whether the layer had a bound."""
    from trlx_tpu.models import transformer

    cfg = _cfg(
        num_experts=8, num_experts_per_tok=K, moe_capacity_factor=0.0,
        dtype=dtype, param_dtype=dtype, **_DROPLESS_LAYERS[case],
    )
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(3, 12, cfg.hidden_size), dtype)
    mask = jnp.ones((3, 12), jnp.int32)
    if case.startswith("padding"):
        mask = mask.at[0, :5].set(0).at[2, :9].set(0)
    target = jnp.asarray(rs.randn(3, 12, cfg.hidden_size), jnp.float32)
    layer = MoEMLP(cfg)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    if permute is not None:
        monkeypatch.setattr(transformer, "permute_rows", permute)
    if case.startswith("pieces"):
        monkeypatch.setattr(transformer, "MOE_MAX_TOKENS", 8)
        monkeypatch.setattr(transformer, "MOE_PIECE_TOKENS", 12)
        assert transformer.moe_token_pieces(36) == 3

    def loss(p, x):
        def apply(p, x):
            return layer.apply({"params": p}, x, token_mask=mask)

        if case == "checkpoint":
            apply = jax.checkpoint(apply)
        y, aux = apply(p, x)
        if not count_calls:
            aux = aux[:8]
        return jnp.sum(y.astype(jnp.float32) * target) + 0.01 * jnp.sum(aux)

    return loss, params, x


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("case", list(_DROPLESS_LAYERS))
def test_dropless_backward_by_gather_is_the_plain_gathers_transpose(case, dtype, monkeypatch):
    """``permute_rows`` gives its backward as a gather by the inverse
    permutation; the transpose JAX derives from the plain gather is a
    scatter-add into zeros at unique rows. Every output row takes one input
    row and nothing is added, so the gradients are EQUAL, not close."""
    loss, params, x = _dropless_layer_loss(case, dtype, None, monkeypatch)
    value, grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)
    loss, params, x = _dropless_layer_loss(case, dtype, _plain_permute_rows, monkeypatch)
    plain_value, plain = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)
    np.testing.assert_array_equal(np.asarray(value), np.asarray(plain_value))
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == len(jax.tree_util.tree_leaves(params)) + 1
    moved = 0
    for (path, g), q in zip(flat, jax.tree_util.tree_leaves(plain)):
        g, q = np.asarray(g.astype(jnp.float32)), np.asarray(q.astype(jnp.float32))
        assert np.isfinite(g).all(), jax.tree_util.keystr(path)
        np.testing.assert_array_equal(g, q, err_msg=jax.tree_util.keystr(path))
        moved += bool(np.any(g != 0))
    # the comparison is of gradients that are there: x, the router and the
    # three expert kernels all receive one
    assert moved == len(flat)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations carry."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(inner)


@pytest.mark.parametrize("case", ["all_held", "held_share", "checkpoint"])
def test_dropless_backward_holds_no_scatter_of_the_row_buffer(case, monkeypatch):
    """The lowered backward moves the sorted ``[tokens x K, d]`` row buffer by
    gathers alone: the scatters the layer keeps are over ``[E + 1]`` counts
    and the ``[tokens x K]`` inverse permutation, never over rows. The plain
    form, traced the same way, holds the two scatter-adds this test is there
    to keep out, so the walk sees what it looks for."""

    def row_scatters(permute):
        loss, params, x = _dropless_layer_loss(case, jnp.bfloat16, permute, monkeypatch)
        rows = x.shape[0] * x.shape[1] * _DROPLESS_K
        eqns = list(_equations(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x).jaxpr))
        found = [
            e.primitive.name for e in eqns
            if e.primitive.name.startswith("scatter")
            and e.invars[0].aval.ndim == 2 and e.invars[0].aval.shape[0] == rows
        ]
        return found, [e.primitive.name for e in eqns]

    found, names = row_scatters(None)
    assert found == [], found
    assert sum(n == "gather" for n in names) >= 4  # two forward, two backward
    plain, _ = row_scatters(_plain_permute_rows)
    assert plain == ["scatter-add", "scatter-add"], plain


# --- a chip's share of the experts: the row buffers cut to a bound -----------
#
# ``MoEMLP._dropless_rows`` of a layer that holds ``held < E`` experts builds
# its sorted row buffers at ``held_row_bound`` rows where the held rows fit
# (``_held_rows``) and at ``tokens x K`` where they do not (``_all_rows``),
# one ``lax.cond`` (``transformer._either``) forward and one backward. The
# toy layers' 108 assignments are under one 128-row tile and under the
# shortest call that has a bound, and 3 experts of 8 are more than a
# sixteenth, so the tests cut the tile to 8 rows and lift the two floors: 88
# of 108 rows at 3 experts of 8, 56 at 2 of 8, 32 of a 36-assignment piece.

_TAKE = {
    "compact": lambda take_first, first, second, *operands: first(*operands),
    "all_rows": lambda take_first, first, second, *operands: second(*operands),
}
_HELD_SHARE_LAYERS = ["held_share", "padding_held_share", "checkpoint", "pieces"]


def _small_row_tile(monkeypatch, tile=8):
    """The bound's arithmetic at the toy layers' size: a row tile of
    ``tile``, no shortest call, any cut of the rows."""
    from trlx_tpu.models import transformer
    from trlx_tpu.ops import grouped_matmul

    monkeypatch.setattr(grouped_matmul, "ROW_TILE", tile)
    monkeypatch.setattr(transformer, "MOE_HELD_MIN_ROWS", 0)
    monkeypatch.setattr(transformer, "MOE_HELD_MIN_CUT", 1)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("case", _HELD_SHARE_LAYERS)
def test_compact_rows_equal_all_rows_to_the_bit(case, dtype, monkeypatch):
    """The ``cond``'s two bodies, each called directly: the same rows go
    through the same grouped matmuls, the rows past the bound are the zeros
    ``_all_rows``' select writes, and a token's ``K`` results are summed by
    the same einsum, so the value and the gradients with respect to ``x``,
    the router and the three expert kernels are EQUAL, not close."""
    from trlx_tpu.models import transformer

    _small_row_tile(monkeypatch)
    got = {}
    for name, take in _TAKE.items():
        monkeypatch.setattr(transformer, "_either", take)
        loss, params, x = _dropless_layer_loss(case, dtype, None, monkeypatch)
        got[name] = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)
    (value, grads), (want_value, want) = got["compact"], got["all_rows"]
    np.testing.assert_array_equal(np.asarray(value), np.asarray(want_value))
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == 5  # the router, the three expert kernels, x
    for (path, g), q in zip(flat, jax.tree_util.tree_leaves(want)):
        g, q = np.asarray(g.astype(jnp.float32)), np.asarray(q.astype(jnp.float32))
        assert np.isfinite(g).all() and np.any(g != 0), jax.tree_util.keystr(path)
        np.testing.assert_array_equal(g, q, err_msg=jax.tree_util.keystr(path))


def _biased_to_the_held(real_tokens, monkeypatch, tile):
    """A layer whose router bias sends all three choices of every token to
    its three held experts: ``3 x real_tokens`` held rows against a bound of
    ``round_up(2 * ceil(108 * 3 / 8), tile)``."""
    _small_row_tile(monkeypatch, tile)
    cfg = _cfg(num_experts=8, num_experts_per_tok=3, moe_capacity_factor=0.0, moe_experts_held=3,
               moe_first_expert=4, moe_topk_method="noaux_tc", moe_scoring="sigmoid")
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(3, 12, cfg.hidden_size), jnp.float32)
    mask = (jnp.arange(36) < real_tokens).astype(jnp.int32).reshape(3, 12)
    layer = MoEMLP(cfg)
    params = dict(layer.init(jax.random.PRNGKey(1), x)["params"])
    params["router_bias"] = jnp.where((jnp.arange(8) >= 4) & (jnp.arange(8) < 7), 100.0, 0.0)
    return layer, params, x, mask


@pytest.mark.parametrize("real_tokens,compact", [(28, 1.0), (29, 0.0), (36, 0.0)],
                         ids=["held_rows_equal_the_bound", "one_token_over", "every_row_held"])
def test_a_call_over_the_bound_runs_every_row_and_says_so(real_tokens, compact, monkeypatch):
    """``L <= C`` takes the compact body, ``L > C`` the all-rows one, on the
    traced count; either way the result is the one a layer without a bound
    gives (``held_row_bound`` at ``tokens x K``: the program before the
    bound), and ``moe/compact_frac`` (slots 8 and 9 of ``aux``) says which
    ran."""
    from trlx_tpu.models import transformer
    from trlx_tpu.models.transformer import held_row_bound, router_load_summary

    layer, params, x, mask = _biased_to_the_held(real_tokens, monkeypatch, tile=6)
    assert held_row_bound(108, 3, 8) == 84
    run = jax.jit(lambda p, x: layer.apply({"params": p}, x, token_mask=mask))
    y, aux = run(params, x)
    assert float(aux[6]) == 3 * real_tokens  # every real assignment fell on a held expert
    assert (float(aux[8]), float(aux[9])) == (compact, 1.0)
    assert float(router_load_summary(aux, layer.config)[4]) == compact
    monkeypatch.setattr(transformer, "held_row_bound", lambda rows, held, experts: rows)
    want, want_aux = jax.jit(lambda p, x: layer.apply({"params": p}, x, token_mask=mask))(params, x)
    assert want_aux.shape == aux.shape and float(want_aux[9]) == 0.0  # no bound, no choice, no call counted
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
    assert np.any(np.asarray(y) != 0)


def _row_buffers(eqns, rows, tokens, K, widths=(64, 96)):
    """Equations that WRITE a ``tokens x K``-row buffer of feature rows:
    ``[rows, width]`` or ``[tokens, K, width]`` at the toy layers' hidden
    size or expert width; reshapes and casts of one are the same buffer and
    do not count."""
    def is_buffer(aval):
        shape = getattr(aval, "shape", ())
        rows_first = (len(shape) == 2 and shape[0] == rows) or (len(shape) == 3 and shape[:2] == (tokens, K))
        return rows_first and shape[-1] in widths and jnp.issubdtype(aval.dtype, jnp.floating)

    return [
        e.primitive.name for e in eqns
        if e.primitive.name not in ("reshape", "convert_element_type", "custom_vjp_call", "jit")  # the same buffer, or a call that holds the writer
        and any(is_buffer(v.aval) for v in e.outvars)
    ]


@pytest.mark.parametrize("case", ["held_share", "checkpoint"])
def test_compact_rows_hold_no_scatter_and_four_buffers_of_every_row(case, monkeypatch):
    """The compact body and its gradient move rows by gathers alone (no
    ``scatter*`` whose operand has ``tokens x K`` or ``bound`` rows of
    features), and the traced gradient writes a buffer of ``tokens x K``
    rows four times: the gathered results the weighted sum reads, in the
    forward and again where the backward differentiates the body from its
    inputs; their gradient ``dy x gates`` (the einsum's own transpose); and
    the gathered row gradients each token sums (the transpose of
    ``rows_of_tokens``). The all-rows body, walked the same way, writes such
    a buffer some forty times."""
    from trlx_tpu.models import transformer

    _small_row_tile(monkeypatch)
    tokens, K = 36, _DROPLESS_K
    bound = transformer.held_row_bound(tokens * K, 3, 8)
    assert bound == 88

    def walk(take):
        monkeypatch.setattr(transformer, "_either", _TAKE[take])
        loss, params, x = _dropless_layer_loss(case, jnp.bfloat16, None, monkeypatch)
        eqns = list(_equations(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x).jaxpr))
        scatters = [
            e.primitive.name for e in eqns
            if e.primitive.name.startswith("scatter") and e.invars[0].aval.ndim == 2
            and e.invars[0].aval.shape[0] in (tokens * K, bound, bound + 1)
        ]
        return scatters, _row_buffers(eqns, tokens * K, tokens, K)

    scatters, buffers = walk("compact")
    assert scatters == [], scatters
    assert sorted(buffers) == ["gather", "gather", "gather", "transpose"], buffers
    scatters, buffers = walk("all_rows")
    assert scatters == [] and len(buffers) > 40, buffers


_ALL_HELD_PROGRAMS = {
    "bf16": "4449df6e17478673350851b0b03c630fb61dd02de5222d1d2b75974e942c5363",
    "f32": "3caacfd088979e0cd90659f71eaeed2322b2f345ecb438dc701c323cd61eea0a",
    "f32_pieces": "302aedbb690ede31bc0d050353bbc4dca7c5366fcecc8ac8390ef1cfd964e86e",
}


@pytest.mark.parametrize("which", list(_ALL_HELD_PROGRAMS))
def test_a_layer_that_holds_every_expert_traces_the_program_it_had(which, monkeypatch, clean_trace_state):
    """Where no bound applies (every expert held here; more than a sixteenth
    of them, or a short call) the layer has one body and no ``cond``: the jaxpr of its
    value and gradients is, letter for letter, the one recorded from the
    commit before the bound (``dc79a02``; sha256 of ``str(jaxpr)``), whole
    and in three pieces under ``lax.map``."""
    import hashlib

    case = "pieces_all_held" if which.endswith("pieces") else "all_held"
    dtype = jnp.bfloat16 if which == "bf16" else jnp.float32
    loss, params, x = _dropless_layer_loss(case, dtype, None, monkeypatch)
    text = str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(params, x))
    assert "cond[" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == _ALL_HELD_PROGRAMS[which]


def test_held_row_bound_is_twice_the_even_share_up_to_a_tile():
    """The arithmetic of the cells: 8 of 256 experts at a 4096-token piece,
    at train steps of 8192 and 5120 tokens, 8 of 128, 32 of 256 (a quarter
    of the rows), 16 of 64 (half); no bound where twice the even share
    reaches the rows (half the experts or more, every expert), nor for a
    decode step's rows or the toy layers of this file."""
    from trlx_tpu.models.transformer import held_row_bound

    assert held_row_bound(4096 * 8, 8, 256) == 2048
    assert held_row_bound(8192 * 8, 8, 256) == 4096
    assert held_row_bound(5120 * 8, 8, 256) == 2560
    assert held_row_bound(4096 * 8, 8, 128) == 4096
    assert held_row_bound(512 * 8, 8, 256) == 256  # the shortest call with a bound
    assert held_row_bound(4096 * 8, 32, 256) == 8192 and held_row_bound(16384 * 8, 32, 256) == 32768
    assert held_row_bound(8192 * 6, 16, 64) == 24576 and held_row_bound(16384 * 6, 16, 64) == 49152
    assert held_row_bound(64 * 8, 8, 256) == 512 and held_row_bound(128 * 8, 8, 128) == 1024
    assert held_row_bound(8192 * 8, 64, 64) == 8192 * 8
    assert held_row_bound(8192 * 8, 32, 64) == 8192 * 8 and held_row_bound(8192 * 8, 48, 64) == 8192 * 8
    assert held_row_bound(36 * 3, 3, 8) == 108


# --- a larger share of the experts: ONE body, window after window ------------
#
# Where the bound cuts the rows by less than ``MOE_HELD_MIN_CUT`` the layer has
# one body, the experts on a window of ``bound`` sorted rows, in a ``while``
# over the windows that hold a live row (``transformer.held_rows``), forward
# and backward; each assignment reads its row after the loop, once. What it is
# held to is the same layer with no bound (``held_row_bound`` patched to
# ``rows``: every sorted row through ``_all_rows``). ``_windows`` gives the toy
# layers this form whatever their share.


def _windows(monkeypatch, tile=8, factor=2):
    """The bound's arithmetic at the toy layers' size, and the one-body form
    for every share: a row tile of ``tile``, no shortest call, ``factor``
    times the even share, no cut large enough for two bodies."""
    from trlx_tpu.models import transformer
    from trlx_tpu.ops import grouped_matmul

    monkeypatch.setattr(grouped_matmul, "ROW_TILE", tile)
    monkeypatch.setattr(transformer, "MOE_HELD_MIN_ROWS", 0)
    monkeypatch.setattr(transformer, "MOE_HELD_ROWS_FACTOR", factor)
    monkeypatch.setattr(transformer, "MOE_HELD_MIN_CUT", 10**9)


def _no_bound(monkeypatch):
    """Every sorted row through ``_all_rows``: the layer before it had a bound."""
    from trlx_tpu.models import transformer

    monkeypatch.setattr(transformer, "held_row_bound", lambda rows, held, experts: rows)


def _assert_the_layer_without_a_bound(got, want, dtype):
    """The windowed form's ``(value, gradients)`` against the same layer's
    with no bound. The value and the three expert kernels' gradients are
    EQUAL: the same rows under the same gates, a token's ``K`` products taken
    in float32 and added in the einsum's order ``k = 0..K-1``; the rows'
    gradient ``g x gate`` is one product a number. Two sums are taken another
    way and are held to their rounding. A gate's gradient is ``result . g``
    over ``d``, reduced a window at a time where ``_sum_choices``' transpose
    contracts ``[tokens, K, d]``: the same float32 terms in another order
    (it reaches the router and, through it, ``x``). A token's gradient is the
    sum of its ``K`` rows' gradients, taken in float32 and rounded once where
    ``jnp.repeat``'s transpose adds them in the rows' dtype: equal in float32,
    within a bfloat16 unit in the last place (and what ``K - 1`` roundings of
    partial sums leave under cancellation) in bfloat16."""
    (value, grads), (want_value, want) = got, want
    np.testing.assert_array_equal(np.asarray(value), np.asarray(want_value))
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == 5  # the router, the three expert kernels, x
    rtol, atol = (0.0, 1e-6) if dtype == jnp.float32 else (2.0**-7, 2.0**-8)
    for (path, g), q in zip(flat, jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        g, q = np.asarray(g.astype(jnp.float32)), np.asarray(q.astype(jnp.float32))
        assert np.isfinite(g).all() and np.any(g != 0), name
        if "w_" in name:
            np.testing.assert_array_equal(g, q, err_msg=name)
        else:
            np.testing.assert_allclose(g, q, rtol=rtol, atol=atol * np.abs(q).max(), err_msg=name)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("case", _HELD_SHARE_LAYERS)
@pytest.mark.parametrize("K", [_DROPLESS_K, 4, 6, 8], ids=["k3", "k4", "k6", "k8"])
def test_windowed_rows_equal_all_rows_to_the_bit(K, case, dtype, monkeypatch):
    """A call whose held rows fit one window against the same layer with no
    bound, at 3, 4, 6 and 8 experts a token (a token's rows fill an (8, 128)
    tile at 8 alone: ``_sum_live_rows``): the same rows go through the same
    grouped matmuls, the rows past the window are the zeros ``_all_rows``'
    select writes, and a token's ``K`` results are weighed and summed in the
    einsum's order in float32, so the value and the expert kernels' gradients
    are EQUAL, not close; the router's and ``x``'s are held to the rounding
    of the two sums the backward takes another way
    (``_assert_the_layer_without_a_bound``)."""
    _windows(monkeypatch)
    loss, params, x = _dropless_layer_loss(case, dtype, None, monkeypatch, count_calls=False, K=K)
    counted = jax.jit(lambda p, x: MoEMLP(_cfg(
        num_experts=8, num_experts_per_tok=K, moe_capacity_factor=0.0, dtype=dtype,
        param_dtype=dtype, **_DROPLESS_LAYERS[case])).apply({"params": p}, x)[1])(params, x)
    assert float(counted[8]) == float(counted[9]) > 0  # every call had a bound and fitted one window
    got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)
    _no_bound(monkeypatch)
    loss, params, x = _dropless_layer_loss(case, dtype, None, monkeypatch, count_calls=False, K=K)
    want = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)
    _assert_the_layer_without_a_bound(got, want, dtype)


def _biased_to_the_held_windows(real_tokens, monkeypatch, tile, factor=2, dtype=jnp.float32, bias=100.0, K=3):
    """A layer whose router bias sends three choices of every token (all of
    them at ``K`` 3) to its three held experts (``bias`` -100: none of them):
    ``3 x real_tokens`` held rows against a bound of ``round_up(factor *
    ceil(36 * K * 3 / 8), tile)``."""
    _windows(monkeypatch, tile, factor)
    cfg = _cfg(num_experts=8, num_experts_per_tok=K, moe_capacity_factor=0.0, moe_experts_held=3,
               moe_first_expert=4, moe_topk_method="noaux_tc", moe_scoring="sigmoid",
               dtype=dtype, param_dtype=dtype)
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(3, 12, cfg.hidden_size), dtype)
    mask = (jnp.arange(36) < real_tokens).astype(jnp.int32).reshape(3, 12)
    layer = MoEMLP(cfg)
    params = dict(layer.init(jax.random.PRNGKey(1), x)["params"])
    params["router_bias"] = jnp.where((jnp.arange(8) >= 4) & (jnp.arange(8) < 7), bias, 0.0).astype(dtype)
    return layer, params, x, mask


@pytest.mark.parametrize("real_tokens,compact", [(28, 1.0), (29, 0.0), (36, 0.0)],
                         ids=["held_rows_equal_the_bound", "one_token_over", "every_row_held"])
def test_a_call_over_the_bound_runs_two_windows_and_says_so(real_tokens, compact, monkeypatch):
    """``L <= C`` runs one window, ``L > C`` a second (84 rows, then the 3
    or 24 left of a sort whose 108 rows the bound does not divide), on the
    traced count; either way the result is the one a layer without a bound
    gives (``held_row_bound`` at ``tokens x K``): a window's rows land where
    they are computed and a token's ``K`` are summed once, after the loop,
    in the einsum's order (from one window to the bit, in bfloat16, whose
    products are exact in float32: in float32 XLA:CPU fuses a product and
    an addition where it pleases, in the sum's loop and in the einsum's
    contraction differently; from two windows, up to what a grouped matmul
    of another height rounds); and ``moe/compact_frac`` (slots 8 and 9 of
    ``aux``) says whether one window was enough."""
    from trlx_tpu.models.transformer import held_row_bound, router_load_summary

    dtype = jnp.bfloat16 if compact else jnp.float32
    layer, params, x, mask = _biased_to_the_held_windows(real_tokens, monkeypatch, tile=6, dtype=dtype)
    assert held_row_bound(108, 3, 8) == 84
    run = jax.jit(lambda p, x: layer.apply({"params": p}, x, token_mask=mask))
    y, aux = run(params, x)
    assert float(aux[6]) == 3 * real_tokens  # every real assignment fell on a held expert
    assert (float(aux[8]), float(aux[9])) == (compact, 1.0)
    assert float(router_load_summary(aux, layer.config)[4]) == compact
    _no_bound(monkeypatch)
    want, want_aux = jax.jit(lambda p, x: layer.apply({"params": p}, x, token_mask=mask))(params, x)
    assert want_aux.shape == aux.shape and float(want_aux[9]) == 0.0  # no bound, no window, no call counted
    if compact:
        np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-6, atol=1e-7)
    assert np.any(np.asarray(y) != 0)


def _held_to_the_layer_without_a_bound(layer, params, x, mask, how, dtype, calls, monkeypatch):
    """Value and all five gradients of a call that took ``calls`` bounded
    calls of several windows each, against the same layer with no bound:
    float32 rounding in float32, bfloat16's in bfloat16."""
    target = jnp.asarray(np.random.RandomState(6).randn(3, 12, x.shape[-1]), jnp.float32)

    def loss(p, x):
        apply = lambda p, x: layer.apply({"params": p}, x, token_mask=mask)
        y, aux = (jax.checkpoint(apply) if how == "checkpoint" else apply)(p, x)
        return jnp.sum(y.astype(jnp.float32) * target) + 0.01 * jnp.sum(aux[:8]), aux

    (value, aux), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(params, x)
    assert (float(aux[8]), float(aux[9])) == (0.0, calls)  # no call fitted one window
    _no_bound(monkeypatch)
    (want_value, _), want = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(params, x)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(float(value), float(want_value), rtol=tol)
    moved = 0
    for (path, g), q in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(want)):
        g, q = np.asarray(g.astype(jnp.float32)), np.asarray(q.astype(jnp.float32))
        assert np.isfinite(g).all(), jax.tree_util.keystr(path)
        np.testing.assert_allclose(g, q, rtol=tol, atol=tol * np.abs(q).max(), err_msg=jax.tree_util.keystr(path))
        moved += bool(np.any(g != 0))
    assert moved == 5  # the router, the three expert kernels, x (the bias decides, and learns nothing)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("how", ["whole", "checkpoint", "pieces"])
@pytest.mark.parametrize("factor,windows", [(2, 2), (1, 3)], ids=["two_windows", "three_windows"])
def test_windows_of_a_call_over_the_bound_give_the_layer_without_one(factor, windows, how, dtype, monkeypatch):
    """Every token's three choices on the held experts: 108 held rows in
    windows of 84 (84 + 24) and, at once the even share, of 42 (42 + 42 +
    24); in three pieces under ``lax.map`` a piece's 36 in windows of 30 and
    18. Against the layer with no bound: every row goes through the kernels
    it would and is read where it would be, and nothing but an expert
    kernel's gradient is summed over windows (a kernel's rows in another
    order), so value and all five gradients agree to float32 rounding in
    float32 and bfloat16's in bfloat16."""
    from trlx_tpu.models import transformer

    layer, params, x, mask = _biased_to_the_held_windows(36, monkeypatch, tile=6, factor=factor, dtype=dtype)
    rows = 108
    if how == "pieces":
        monkeypatch.setattr(transformer, "MOE_MAX_TOKENS", 8)
        monkeypatch.setattr(transformer, "MOE_PIECE_TOKENS", 12)
        rows, windows = 36, 2
    bound = transformer.held_row_bound(rows, 3, 8)
    assert bound == {(108, 2): 84, (108, 1): 42, (36, 2): 30, (36, 1): 18}[rows, factor]
    assert -(-rows // bound) == windows
    _held_to_the_layer_without_a_bound(layer, params, x, mask, how, dtype, 108 // rows, monkeypatch)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("windows", [2, 3], ids=["two_windows", "three_windows"])
@pytest.mark.parametrize("K", [4, 6, 8], ids=["k4", "k6", "k8"])
def test_windows_at_four_six_and_eight_choices_give_the_layer_without_one(K, windows, dtype, monkeypatch):
    """The same at 4, 6 and 8 experts a token, where a token's rows do not
    (4, 6) and do (8) fill a tile of eight: three of every token's choices
    on the three held experts, 108 held rows of ``36 x K``, in windows of 54
    and of 36 (the bound set by hand: once the even share is two windows at
    4 choices and one at 6 and 8)."""
    from trlx_tpu.models import transformer

    layer, params, x, mask = _biased_to_the_held_windows(36, monkeypatch, tile=6, dtype=dtype, K=K)
    bound = 108 // windows
    monkeypatch.setattr(transformer, "held_row_bound", lambda rows, held, experts: bound)
    _held_to_the_layer_without_a_bound(layer, params, x, mask, "whole", dtype, 1, monkeypatch)


@pytest.mark.parametrize("why", ["no_real_token", "no_choice_falls_here"])
def test_a_call_with_no_held_row_runs_no_window(why, monkeypatch):
    """Zero held rows are zero trips of both loops: zeros out, zero
    gradients, nothing unwritten let through."""
    real_tokens, bias = (0, 100.0) if why == "no_real_token" else (36, -100.0)
    layer, params, x, mask = _biased_to_the_held_windows(real_tokens, monkeypatch, tile=6, bias=bias)

    def loss(p, x):
        y, aux = layer.apply({"params": p}, x, token_mask=mask)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), (y, aux)

    (_, (y, aux)), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(params, x)
    assert float(aux[4]) == 3 * real_tokens and float(aux[6]) == 0.0  # asked for, and none of them here
    assert (float(aux[8]), float(aux[9])) == (1.0, 1.0)  # a call with a bound, and nothing overflowed it
    assert not np.asarray(y).any()
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert not np.asarray(g).any(), jax.tree_util.keystr(path)  # zeros, so no NaN either


@pytest.mark.parametrize("case", ["held_share", "checkpoint"])
def test_windowed_rows_hold_no_scatter_and_four_buffers_of_every_row(case, monkeypatch):
    """The bounded layer and its gradient move rows by gathers alone (no
    ``scatter*`` whose operand has ``tokens x K`` or ``bound`` rows of
    features). Since PR 62 the four buffers of the name are two a direction:
    one uninitialised ``[windows x bound, d]`` ROOM (the results by window
    forward; the row gradients by window backward, beside a ``[windows x
    bound]`` float32 vector of the gates' gradients: the backward's second
    room, the results again, went with its gather), written by the ``while``
    body a window of ``bound`` rows at a time, and ONE gather of it after the
    loop, choice-major: a token's ``K`` rows are ``K`` whole slices of what
    the gather wrote and are summed as they are read. Nothing else writes
    ``tokens x K`` rows of features, inside the loops or outside, and no
    equation has a ``[tokens, K, d]`` operand or result, so no ``d``-wide
    array is reshaped to or from that shape. The layer with no bound, walked
    the same way, writes such a buffer some thirty times, views it ``[tokens,
    K, d]`` and has no ``while``."""
    from trlx_tpu.models import transformer

    _windows(monkeypatch)
    tokens, K = 36, _DROPLESS_K
    bound = transformer.held_row_bound(tokens * K, 3, 8)
    assert bound == 88

    def walk():
        loss, params, x = _dropless_layer_loss(case, jnp.bfloat16, None, monkeypatch)
        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x).jaxpr
        eqns = list(_equations(jaxpr))
        scatters = [
            e.primitive.name for e in eqns
            if e.primitive.name.startswith("scatter") and e.invars[0].aval.ndim == 2
            and e.invars[0].aval.shape[0] in (tokens * K, bound, bound + 1, 2 * bound)
        ]
        names = [e.primitive.name for e in eqns]
        in_loops = [inner for e in eqns if e.primitive.name == "while" for inner in _equations(e.params["body_jaxpr"].jaxpr)]
        rooms = sorted(e.outvars[0].aval.shape for e in eqns if e.primitive.name == "empty")
        by_choice = [
            e.primitive.name for e in eqns for v in (*e.invars, *e.outvars)
            if getattr(v.aval, "shape", ())[:2] == (tokens, K) and len(v.aval.shape) == 3
        ]
        return (scatters, _row_buffers(eqns, tokens * K, tokens, K), _row_buffers(in_loops, tokens * K, tokens, K),
                rooms, by_choice, names.count("while"), names.count("cond"))

    scatters, buffers, in_loops, rooms, by_choice, whiles, conds = walk()
    assert scatters == [], scatters
    assert buffers == ["gather", "gather"] and in_loops == [], buffers
    assert rooms == sorted([(2 * bound, 64), (2 * bound, 64), (2 * bound,)]), rooms  # the toy layers' hidden size
    assert by_choice == [], by_choice
    assert (whiles, conds) == (2, 0)  # one body a direction, on ``bound`` rows
    _no_bound(monkeypatch)
    scatters, buffers, in_loops, rooms, by_choice, whiles, conds = walk()
    assert scatters == [] and len(buffers) > 30, buffers
    assert "reshape" in by_choice and rooms == []
    assert (whiles, conds) == (0, 0)


def test_a_frozen_layers_kernel_gradients_are_not_computed(monkeypatch):
    """A frozen expert kernel reaches the layer through ``stop_gradient``
    (the train step's rule for a leaf the mask marks False), so its gradient
    is only ever added to itself in the backward loop's carry. The compiled
    gradient holds no product with a ``[held, d, f]`` or ``[held, f, d]``
    result: the compiler takes the carry and its grouped matmul out. With
    the kernels differentiated, the same walk finds the three."""
    import re

    _windows(monkeypatch)
    loss, params, x = _dropless_layer_loss("held_share", jnp.float32, None, monkeypatch)
    kernels = {k: v for k, v in params.items() if k != "router"}

    def kernel_gradients(fn, *args):
        text = jax.jit(jax.grad(fn, argnums=(0, 1))).lower(*args).compile().as_text()
        return re.findall(r"= f32\[3,(?:64,96|96,64)\]\S* (?:dot|ragged-dot|convolution)\(", text)

    frozen = lambda router, x: loss({"router": router, **jax.lax.stop_gradient(kernels)}, x)
    assert len(kernel_gradients(loss, params, x)) >= 3
    assert kernel_gradients(frozen, params["router"], x) == []


@pytest.mark.parametrize("method", ["grpo", "ppo"])
def test_olmoe_rl_step_through_train(method, tmp_path):
    """One collection and two optimizer steps on ``builtin:olmoe-test``
    through ``trlx_tpu.train()``: the normal trainer, sampler, cache and
    learn loop, with the dropless counters in the step stats."""
    import trlx_tpu
    from trlx_tpu.data import default_configs

    base = {"grpo": default_configs.default_grpo_config,
            "ppo": default_configs.default_ppo_config}[method]()
    method_kw = dict(num_rollouts=16, chunk_size=16, ppo_epochs=1,
                     gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0, do_sample=True))
    if method == "grpo":
        method_kw["group_size"] = 4
    config = base.evolve(
        train=dict(seq_length=24, batch_size=8, total_steps=2, epochs=100, eval_interval=100,
                   checkpoint_interval=1000, checkpoint_dir=str(tmp_path / "ckpt"), tracker=None),
        model=dict(model_path="builtin:olmoe-test"),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        method=method_kw,
    )
    seen = []

    def hook(trainer):
        class Tracker:
            def log(self, stats, step=None):
                seen.append(dict(stats))

            def finish(self):
                pass

        trainer.tracker = Tracker()

    trainer = trlx_tpu.train(
        reward_fn=lambda samples, prompts, outputs, **kw: [float(len(set(o))) for o in outputs],
        prompts=["abcdefgh" * 2] * 16, eval_prompts=["abcdefgh" * 2] * 2,
        config=config, init_trainer_hook=hook,
    )
    assert trainer.iter_count == 2
    assert trainer.tcfg.qk_norm and trainer.tcfg.moe_capacity_factor == 0
    steps = [s for s in seen if "moe/dropped_frac" in s]
    assert len(steps) == 2
    for s in steps:
        assert float(s["moe/dropped_frac"]) == 0.0
        assert 1.0 <= float(s["moe/load_max_over_mean"]) <= trainer.tcfg.num_experts
        assert np.isfinite(float(s["losses/total_loss"]))


def test_olmoe_cell_rehearsal():
    """The benchmark cell ``olmoe7b_grpo_decode`` end to end on the CPU at
    the configuration's toy widths: the same harness, trainer and checks
    against ``chipbench/reference/olmoe.py`` as on the chip. Its numbers mean
    nothing; ``correct`` and ``programs_compiled`` do."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "olmoe7b_grpo_decode", "--seed",
         "3000000019", "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"] and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
