"""The PPO learner loss as the trainer composes it:
``PPOConfig.get_advantages_and_returns`` then ``PPOConfig.loss`` on fixed
``[B, R]`` blocks under a response mask.

Held here, across the mask shapes the epilogue can meet (random holes, a row
with no token, a batch with no token, one token a row), importance weighting,
bf16 rollout values and ``[1, 1]`` blocks: loss, gradients and stats are
finite, and a masked token reaches nothing — whatever finite numbers stand
under the mask in any operand of ``loss``, loss, gradients and every stat
come out the same to the bit, and the gradient at a masked position is zero.

``get_advantages_and_returns`` is outside that statement on purpose: GAE reads
``old_values`` and ``rewards`` unmasked (its docstring: padding carries zeros,
which the rollout store's collation provides), so the advantages and returns
are computed once from the operands as built and handed to ``loss`` as the
trainer does.

Every path is compared jit-to-jit with every operand a runtime argument, as
the trainer passes batch arrays: a jitted function that closes over a bf16
``old_values`` lets XLA fold the clip bounds at another precision.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.models.ppo import PPOConfig

B, R = 7, 13
MASK_KINDS = ("random", "allmasked_row", "all_zero", "single_token")


def _method(**kw):
    return PPOConfig(name="PPOConfig", **kw)


def _mask(kind, rs, b=B, r=R):
    if kind == "all_zero":
        return np.zeros((b, r), np.float32)
    if kind == "single_token":
        m = np.zeros((b, r), np.float32)
        m[np.arange(b), rs.randint(0, r, b)] = 1.0
        return m
    m = (rs.rand(b, r) > 0.3).astype(np.float32)
    if kind == "allmasked_row":
        m[0] = 0.0
    return m


def _operands(mask_kind="random", b=B, r=R, ov_dtype=None, seed=0):
    rs = np.random.RandomState(seed)
    lp = jnp.asarray(rs.randn(b, r).astype(np.float32) * 0.1)
    v = jnp.asarray(rs.randn(b, r).astype(np.float32))
    olp = lp + jnp.asarray(rs.randn(b, r).astype(np.float32) * 0.05)
    ov = jnp.asarray(rs.randn(b, r).astype(np.float32))
    if ov_dtype is not None:
        ov = ov.astype(ov_dtype)
    rw = jnp.asarray(rs.randn(b, r).astype(np.float32) * 0.05)
    mask = jnp.asarray(_mask(mask_kind, rs, b, r))
    return lp, v, olp, ov, rw, mask


def _behavior(ops, seed=1):
    rs = np.random.RandomState(seed)
    olp = ops[2]
    return olp + jnp.asarray(rs.randn(*olp.shape).astype(np.float32) * 0.03)


def _targets(method, ops):
    """``(advantages, returns)`` of the operands as built, jitted."""
    _, _, _, ov, rw, mask = ops[:6]
    return jax.jit(method.get_advantages_and_returns)(ov, rw, mask)


def _loss_of(method):
    def loss(lp, v, olp, ov, adv, ret, mask, blp=None):
        return method.loss(
            logprobs=lp, values=v, old_logprobs=olp, old_values=ov,
            advantages=adv, returns=ret, mask=mask, behavior_logprobs=blp,
        )

    return loss


def _assert_masked_tokens_reach_nothing(method, ops):
    """``ops`` is ``_operands()``'s tuple, with ``_behavior()``'s array
    appended for the importance-weighted loss."""
    lp, v, olp, ov, _, mask = ops[:6]
    adv, ret = _targets(method, ops)
    args = (lp, v, olp, ov, adv, ret, mask) + tuple(ops[6:])
    loss = _loss_of(method)
    value = jax.jit(loss)
    grads = jax.jit(jax.grad(lambda *a: loss(*a)[0], argnums=(0, 1)))

    l0, s0 = value(*args)
    g0 = grads(*args)
    # a batch with no token has no masked minimum or maximum:
    # get_tensor_stats answers +inf and -inf there by construction
    no_token = not bool(mask.sum())
    assert np.isfinite(l0)
    for k, x in s0.items():
        if no_token and k.rsplit("/", 1)[-1] in ("min", "max"):
            continue
        assert np.isfinite(np.asarray(x)).all(), k
    for g in g0:
        assert np.isfinite(np.asarray(g)).all()
        assert (np.asarray(g)[np.asarray(mask) == 0] == 0.0).all()

    rs = np.random.RandomState(7)
    other = tuple(
        a if a is mask else jnp.where(
            mask > 0, a, jnp.asarray(rs.randn(*a.shape) * 3.0).astype(a.dtype)
        )
        for a in args
    )
    l1, s1 = value(*other)
    g1 = grads(*other)
    assert jnp.array_equal(l0, l1), "loss moved with a masked token"
    assert set(s0) == set(s1)
    for k in s0:
        assert jnp.array_equal(s0[k], s1[k]), f"stat {k} moved with a masked token"
    assert jnp.array_equal(g0[0], g1[0]), "d/d logprobs moved with a masked token"
    assert jnp.array_equal(g0[1], g1[1]), "d/d values moved with a masked token"


@pytest.mark.parametrize("mask_kind", MASK_KINDS)
def test_masked_tokens_reach_nothing_across_mask_shapes(mask_kind):
    """Every mask edge case the whitening/GAE epilogue can hit: random
    holes, a fully-masked row, an all-masked batch, single-token rows."""
    _assert_masked_tokens_reach_nothing(
        _method(dist_sketches=True), _operands(mask_kind)
    )


def test_masked_tokens_reach_nothing_with_importance_weighting():
    """behavior_logprobs (async collection): the truncated ratio and its
    ``iw/*`` stats read the sampler's logprobs under the mask only."""
    ops = _operands()
    _assert_masked_tokens_reach_nothing(
        _method(iw_correction="clip"), ops + (_behavior(ops),)
    )


def test_masked_tokens_reach_nothing_bf16_old_values():
    """Rollout values stored in bf16 keep their dtype into the clip bounds."""
    _assert_masked_tokens_reach_nothing(
        _method(), _operands(ov_dtype=jnp.bfloat16)
    )


def test_masked_tokens_reach_nothing_degenerate_shapes():
    _assert_masked_tokens_reach_nothing(
        _method(), _operands(b=1, r=1, mask_kind="random")
    )
    _assert_masked_tokens_reach_nothing(
        _method(), _operands(b=1, r=1, mask_kind="all_zero")
    )


def test_sketches_ride_without_perturbing_loss_or_grads():
    """dist_sketches on vs off leaves loss and grads byte-identical (the
    sketches are a stop-gradient'd epilogue of the loss's own
    intermediates) and adds only ``dist/*`` keys."""
    ops = _operands()
    on, off = _method(dist_sketches=True), _method(dist_sketches=False)
    lp, v, olp, ov, _, mask = ops
    adv, ret = _targets(on, ops)
    args = (lp, v, olp, ov, adv, ret, mask)

    l_on, s_on = jax.jit(_loss_of(on))(*args)
    l_off, s_off = jax.jit(_loss_of(off))(*args)
    assert jnp.array_equal(l_on, l_off)
    g_on = jax.jit(jax.grad(
        lambda *a: _loss_of(on)(*a)[0], argnums=(0, 1)
    ))(*args)
    g_off = jax.jit(jax.grad(
        lambda *a: _loss_of(off)(*a)[0], argnums=(0, 1)
    ))(*args)
    assert jnp.array_equal(g_on[0], g_off[0])
    assert jnp.array_equal(g_on[1], g_off[1])
    sketch_keys = {k for k in s_on if k.startswith("dist/")}
    assert sketch_keys and set(s_on) - sketch_keys == set(s_off)
    for k in s_off:
        assert jnp.array_equal(s_on[k], s_off[k]), k


def test_returns_and_advantages_are_stop_gradiented():
    """GAE targets are regression targets, not predictions: no gradient
    may flow from the loss back through ``returns``/``advantages`` into
    ``old_values``, by this function's own doing rather than by the
    trainer's call pattern."""
    m = _method()
    _, _, _, ov, rw, mask = _operands()

    for pick in (0, 1):  # advantages, returns
        g = jax.grad(
            lambda o: m.get_advantages_and_returns(o, rw, mask)[pick].sum()
        )(ov)
        assert (np.asarray(g) == 0.0).all()

    # grad-equality regression at the loss level: d(loss)/d(values) is
    # identical whether or not old_values is treated as differentiable
    lp, v, olp, ov, rw, mask = _operands()

    def loss_of(values, old_values):
        adv, ret = m.get_advantages_and_returns(old_values, rw, mask)
        return m.loss(
            logprobs=lp, values=values, old_logprobs=olp,
            old_values=old_values, advantages=adv, returns=ret, mask=mask,
        )[0]

    g_live = jax.jit(jax.grad(loss_of, argnums=0))(v, ov)
    g_const = jax.jit(jax.grad(
        lambda values: loss_of(values, jax.lax.stop_gradient(ov))
    ))(v)
    assert jnp.array_equal(g_live, g_const)
