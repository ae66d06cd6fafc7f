"""The train step takes no gradient with respect to a leaf the mask freezes
(``trainer/base.py::_build_train_step``; ISSUE 41).

Five jobs that freeze something, each through the one rule that reads
``trainer.param_mask``: PPO on ``builtin:gpt2-test`` and on
``builtin:falconh1-test`` with one unfrozen block, ILQL (its target-Q heads),
seq2seq PPO (``seq2seq_trainable_mask``: embedding and encoder frozen) and
PPO with LoRA adapters on ``builtin:pangu-test`` (PR 40's job). For each:
the update is the arithmetic of a step that differentiates the whole tree,
bit for bit; the logged norm is the trained leaves'; the program holds no
weight-gradient product of a frozen kernel. Beside them: a mask that
freezes nothing leaves the program as it was, the gauge
``learn/grad_param_frac`` says how far the rule engages, and a
``scan_layers`` stack with a per-layer vector mask behaves as it did.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from trlx_tpu import perf
from trlx_tpu.data.default_configs import (
    default_grpo_config,
    default_ilql_config,
    default_ppo_config,
)
from trlx_tpu.models.builder import grad_param_frac, is_frozen

B, P, N = 8, 13, 9
FLOAT32 = dict(param_dtype="float32", compute_dtype="float32")


def _config(base, **model):
    return base.evolve(
        train=dict(tracker=None, seq_length=P + N, batch_size=B),
        model=model,
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        parallel=FLOAT32,
    )


JOBS = {
    "ppo-gpt2": lambda: _config(
        default_ppo_config(), model_path="builtin:gpt2-test", num_layers_unfrozen=1),
    "ppo-falconh1": lambda: _config(
        default_ppo_config(), model_path="builtin:falconh1-test", num_layers_unfrozen=1),
    "ilql": lambda: _config(
        default_ilql_config(), model_path="builtin:gpt2-test", num_layers_unfrozen=1),
    "seq2seq": lambda: _config(
        default_ppo_config(), model_path="builtin:t5-test", model_arch_type="seq2seq",
        num_layers_unfrozen=1),
    "lora": lambda: _config(
        default_ppo_config(), model_path="builtin:pangu-test", num_layers_unfrozen=1,
        model_extra_kwargs=dict(moe_experts_held=2, moe_first_expert=2),
        peft_kwargs=dict(peft_type="lora", r=4, lora_alpha=8,
                         modified_modules=["q_a_proj", "q_b_proj", "kv_a_proj", "o_proj"])),
}


def _trainer(config):
    from trlx_tpu.trainer import get_trainer
    import trlx_tpu.trainer.grpo  # noqa: F401  (registration)
    import trlx_tpu.trainer.ilql  # noqa: F401
    import trlx_tpu.trainer.ppo  # noqa: F401

    return get_trainer(config.train.trainer)(
        config, reward_fn=lambda samples, **kw: [0.0] * len(samples))


def _batch(trainer, seed=0):
    """A fixed batch of the trainer's loss contract: random tokens, rows of
    uneven real lengths, small float targets."""
    rng = np.random.RandomState(seed)
    vocab = int(trainer.tcfg.vocab_size)
    out = {}
    for key, sds in perf._train_batch_sds(type(trainer).__name__.lower(), B, P, N).items():
        rows, width = sds.shape[0], sds.shape[-1]
        if key.endswith("mask"):
            real = rng.randint(width // 2, width + 1, size=rows)
            ramp = np.arange(width)[None, :]
            # prompts pad on the left, everything else on the right
            mask = ramp >= width - real[:, None] if key == "query_mask" else ramp < real[:, None]
            out[key] = mask.astype(np.int32)
        elif key in ("states_ixs", "actions_ixs"):
            out[key] = np.tile(np.arange(P - 1, P - 1 + width, dtype=np.int32), (rows, 1))
        elif key == "dones":
            out[key] = np.concatenate(
                [np.ones((rows, width - 1), np.int32), np.zeros((rows, 1), np.int32)], axis=1)
        elif np.issubdtype(sds.dtype, np.integer):
            out[key] = rng.randint(0, min(vocab, 250), size=sds.shape).astype(np.int32)
        else:
            out[key] = (0.1 * rng.standard_normal(sds.shape)).astype(np.float32)
    return out


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def _paths(tree):
    return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(tree)]


def _whole_tree_step(trainer):
    """The step as it was: ``jax.value_and_grad`` over EVERY leaf, then the
    same optimizer, which sends the frozen leaves' gradients to zero."""

    def step(state, batch, loss_scale):
        rng, step_rng = jax.random.split(state.rng)

        def loss(params):
            value, stats = trainer.loss_fn(params, batch, step_rng)
            return value * loss_scale, stats

        (_, _), grads = jax.value_and_grad(loss, has_aux=True)(state.params)
        updates, opt_state = trainer.optimizer.update(grads, state.opt_state, state.params)
        return optax.apply_updates(state.params, updates), opt_state, grads

    return jax.jit(step)


def _build_without_the_rule(trainer):
    """``_build_train_step`` as it lowers when the mask freezes nothing: the
    whole tree differentiated, the trainer's own (masked) optimizer."""
    mask = trainer.param_mask
    trainer.param_mask = None
    try:
        return trainer._build_train_step()
    finally:
        trainer.param_mask = mask


def _dot_result_shapes(jaxpr, out=None):
    """Result shapes of every ``dot_general`` of a jaxpr, sub-jaxprs included."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(tuple(eqn.outvars[0].aval.shape))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _dot_result_shapes(inner, out)
    return out


@pytest.fixture(scope="module", params=list(JOBS))
def job(request):
    trainer = _trainer(JOBS[request.param]())
    batch = jax.tree_util.tree_map(jnp.asarray, _batch(trainer))
    return types.SimpleNamespace(name=request.param, trainer=trainer, batch=batch)


def test_the_mask_freezes_something(job):
    mask = jax.tree_util.tree_leaves(job.trainer.param_mask)
    assert any(is_frozen(m) for m in mask) and not all(is_frozen(m) for m in mask)
    names = [p for p, m in zip(_paths(job.trainer.param_mask), mask) if is_frozen(m)]
    expected = {"ppo-gpt2": "['h_0']", "ppo-falconh1": "['h_0']", "ilql": "target_q_head",
                "seq2seq": "['enc_0']", "lora": "['wte']"}[job.name]
    assert any(expected in p for p in names), names


def test_update_is_the_whole_tree_steps_bit_for_bit(job):
    """(a) and (b): trained leaves and every optimizer moment equal those of
    a step whose gradients come from ``jax.grad`` over the whole tree; frozen
    leaves are their inputs; the logged norm is the trained leaves' alone."""
    trainer, scale = job.trainer, np.float32(1.0)
    before = jax.tree_util.tree_map(np.asarray, trainer.state.params)
    want_params, want_opt, grads = _whole_tree_step(trainer)(trainer.state, job.batch, scale)
    got, stats = trainer._build_train_step()(_copy(trainer.state), job.batch, scale)

    mask = jax.tree_util.tree_leaves(trainer.param_mask)
    moved = 0
    for path, m, b, w, g in zip(_paths(before), mask, jax.tree_util.tree_leaves(before),
                                jax.tree_util.tree_leaves(want_params),
                                jax.tree_util.tree_leaves(got.params)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=path)
        if is_frozen(m):
            np.testing.assert_array_equal(np.asarray(g), b, err_msg=path)
        else:
            moved += int(not np.array_equal(np.asarray(g), b))
    assert moved > 0
    assert jax.tree_util.tree_structure(got.opt_state) == jax.tree_util.tree_structure(want_opt)
    for path, w, g in zip(_paths(want_opt), jax.tree_util.tree_leaves(want_opt),
                          jax.tree_util.tree_leaves(got.opt_state)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=path)

    leaves = jax.tree_util.tree_leaves(grads)
    trained = float(optax.global_norm([g for g, m in zip(leaves, mask) if not is_frozen(m)]))
    assert float(stats["gradients/global_norm"]) == pytest.approx(trained, rel=1e-6)
    # the whole tree's norm is another number: the frozen leaves had gradients
    assert float(optax.global_norm(leaves)) > trained * (1 + 1e-4)


def test_no_weight_gradient_product_of_a_frozen_kernel(job):
    """(c): every frozen 2-D matmul leaf lost its weight-gradient
    ``dot_general`` (but ILQL's target-Q heads, which the loss itself reads
    under ``stop_gradient``: they never had one). Counted by the result's
    size, since a projection applied by heads gets its gradient in the heads'
    shape, over the products whose result is no activation."""
    from chipbench.flops import MATMUL_LEAVES

    trainer, scale = job.trainer, np.float32(1.0)
    frozen = {}
    for (path, p), m in zip(jax.tree_util.tree_leaves_with_path(trainer.state.params),
                            jax.tree_util.tree_leaves(trainer.param_mask)):
        if (is_frozen(m) and p.ndim == 2 and getattr(path[-1], "key", "") in MATMUL_LEAVES
                and "target_q_head" not in jax.tree_util.keystr(path)):
            frozen[p.size] = frozen.get(p.size, 0) + 1
    assert frozen
    tokens = {B * P, B * N, B * (P + N)}

    def counts(fn):
        shapes = _dot_result_shapes(jax.make_jaxpr(fn)(trainer.state, job.batch, scale).jaxpr)
        weights = [s for s in shapes if not (tokens & set(s)) and not (len(s) > 2 and s[0] == B)]
        return {size: sum(int(np.prod(s)) == size for s in weights) for size in frozen}

    with_rule, without = counts(trainer._build_train_step()), counts(_build_without_the_rule(trainer))
    assert {size: without[size] - with_rule[size] for size in frozen} == frozen


# the yardstick's jobs at four times the toy widths: at a hidden size of 64
# XLA's count is a tenth elementwise work (a frozen bias's gradient fused
# with its activation's backward), which the 5% would be spent on
COUNTED = {
    "ppo-gpt2": dict(model_path="builtin:gpt2-test",
                     model_extra_kwargs=dict(hidden_size=256, intermediate_size=1024)),
    "ppo-falconh1": dict(model_path="builtin:falconh1-test",
                         model_extra_kwargs=dict(hidden_size=256, head_dim=64, intermediate_size=512,
                                                 mamba_head_dim=64)),
}


@pytest.mark.parametrize("name", list(COUNTED))
def test_flops_fall_by_what_the_yardstick_stopped_charging(name):
    """(c): XLA's count of the compiled step falls against the whole-tree
    step by the frozen kernels' ``2ab`` a slot: the weight gradients that
    ``chipbench/flops.py`` does not charge (``Model.trained``), so that the
    yardstick and the program cannot drift apart again."""
    from chipbench import flops

    trainer = _trainer(_config(default_ppo_config(), num_layers_unfrozen=1, **COUNTED[name]))
    batch = jax.tree_util.tree_map(jnp.asarray, _batch(trainer))
    args = (trainer.state, batch, np.float32(1.0))

    def compiled_flops(fn):
        cost = fn.lower(*args).compile().cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        return float(cost["flops"])

    fell = compiled_flops(_build_without_the_rule(trainer)) - compiled_flops(trainer._build_train_step())
    model = flops.Model(trainer)
    uncharged = B * sum(
        value for i in range(model.n_layers)
        for path, value in model.layer(i, P + N, {})["matmuls"].items()
        if not model.trained(i, path))
    assert uncharged > 0
    assert fell == pytest.approx(uncharged, rel=0.05)


def test_a_mask_that_freezes_nothing_leaves_the_program_as_it_was():
    """(d): a GRPO policy has no ``backbone`` key, its mask marks every leaf,
    and the step lowers to the jaxpr it has with no mask at all."""
    trainer = _trainer(_config(
        default_grpo_config(), model_path="builtin:gpt2-test", num_layers_unfrozen=1))
    assert not any(is_frozen(m) for m in jax.tree_util.tree_leaves(trainer.param_mask))
    batch = jax.tree_util.tree_map(jnp.asarray, _batch(trainer))
    args = (trainer.state, batch, np.float32(1.0))
    with_mask = str(jax.make_jaxpr(trainer._build_train_step())(*args))
    assert trainer._grad_param_frac == 1.0
    assert with_mask == str(jax.make_jaxpr(_build_without_the_rule(trainer))(*args))


def test_gauge_is_the_masks_own_count(job):
    """(e), the count: differentiated parameters over parameters held."""
    trainer = job.trainer
    trainer._build_train_step()
    sizes = [int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(trainer.state.params)]
    mask = jax.tree_util.tree_leaves(trainer.param_mask)
    want = sum(n for n, m in zip(sizes, mask) if m is not False) / sum(sizes)
    assert 0.0 < want < 1.0
    assert trainer._grad_param_frac == pytest.approx(want, rel=1e-12)
    assert grad_param_frac(trainer.state.params, None) == 1.0


def test_gauge_rides_every_step_record(tmp_path):
    """(e), the record: ``learn/grad_param_frac`` beside ``learn/pad_frac``."""
    import trlx_tpu.trlx as trlx

    config = JOBS["ppo-gpt2"]().evolve(
        train=dict(seq_length=32, batch_size=4, total_steps=2, eval_interval=10,
                   checkpoint_interval=10, epochs=1, save_best=False,
                   checkpoint_dir=str(tmp_path / "ckpts"), logging_dir=str(tmp_path / "logs")),
        method=dict(num_rollouts=8, chunk_size=8, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=8, min_new_tokens=8, do_sample=True)))
    records = []

    def hook(trainer):
        trainer.tracker = types.SimpleNamespace(
            log=lambda stats, step=None: records.append(dict(stats)), finish=lambda: None)

    trainer = trlx.train(
        reward_fn=lambda samples, **kw: [float(len(s) % 3) for s in samples],
        prompts=["abcdefgh" * 2] * 8, config=config, init_trainer_hook=hook)
    steps = [r for r in records if "time/train_step" in r]
    assert len(steps) == 2
    want = grad_param_frac(trainer.state.params, trainer.param_mask)
    assert 0.0 < want < 1.0
    for record in steps:
        assert "learn/pad_frac" in record
        assert record["learn/grad_param_frac"] == pytest.approx(want, rel=1e-12)


def test_scanned_stack_with_a_vector_mask_trains_and_freezes_the_layers_it_did():
    """(f): under ``scan_layers`` the stacked leaves carry a per-layer 0/1
    vector; they are differentiated whole and masked in the optimizer, so
    the top layer's rows move and the rows below stay bit-identical."""
    trainer = _trainer(_config(
        default_ppo_config(), model_path="builtin:gpt2-test", num_layers_unfrozen=1,
        model_extra_kwargs=dict(scan_layers=True)))
    stacked = trainer.param_mask["backbone"]["h_scan"]
    vectors = [m for m in jax.tree_util.tree_leaves(stacked) if not isinstance(m, (bool, np.bool_))]
    assert vectors and all(np.array_equal(v, vectors[0]) for v in vectors)
    top = int(trainer.tcfg.num_layers) - 1
    assert vectors[0].tolist() == [0.0] * top + [1.0]
    batch = jax.tree_util.tree_map(jnp.asarray, _batch(trainer))
    before = jax.tree_util.tree_map(np.asarray, trainer.state.params["backbone"]["h_scan"])
    step = trainer._build_train_step()
    # the vector leaves count as differentiated: nothing else is frozen here
    assert trainer._grad_param_frac == 1.0
    got, stats = step(_copy(trainer.state), batch, np.float32(1.0))
    assert np.isfinite(float(stats["gradients/global_norm"]))
    after = jax.tree_util.tree_map(np.asarray, got.params["backbone"]["h_scan"])
    moved = 0
    for path, b, a in zip(_paths(before), jax.tree_util.tree_leaves(before),
                          jax.tree_util.tree_leaves(after)):
        np.testing.assert_array_equal(a[:top], b[:top], err_msg=path)
        moved += int(not np.array_equal(a[top], b[top]))
    assert moved > 0


def test_accumulated_step_matches_the_whole_tree_step():
    """Under ``grad_accum > 1`` the scan carries a gradient tree of the
    parameters' shape, zeros at the frozen leaves: the update is still the
    whole-tree step's, bit for bit, and the frozen leaves stay put."""
    trainer = _trainer(JOBS["ppo-gpt2"]().evolve(train=dict(grad_accum=2)))
    batch = jax.tree_util.tree_map(jnp.asarray, _batch(trainer))
    scale = np.float32(1.0)
    before = jax.tree_util.tree_map(np.asarray, trainer.state.params)
    want, _ = _build_without_the_rule(trainer)(_copy(trainer.state), batch, scale)
    got, stats = trainer._build_train_step()(_copy(trainer.state), batch, scale)
    assert np.isfinite(float(stats["gradients/global_norm"]))
    for path, m, b, w, g in zip(_paths(before), jax.tree_util.tree_leaves(trainer.param_mask),
                                jax.tree_util.tree_leaves(before),
                                jax.tree_util.tree_leaves((want.params)),
                                jax.tree_util.tree_leaves((got.params))):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=path)
        if is_frozen(m):
            np.testing.assert_array_equal(np.asarray(g), b, err_msg=path)
    for w, g in zip(jax.tree_util.tree_leaves(want.opt_state), jax.tree_util.tree_leaves(got.opt_state)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
