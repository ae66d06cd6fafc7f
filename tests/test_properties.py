"""Property-based tests over the algorithm math (hypothesis), mirroring the
reference's strategy (``tests/test_models.py:433-603`` uses hypothesis over
tensor shapes for indexing equivalence, sync, and loss-doesn't-crash;
SURVEY.md §4): ``batched_index_select`` vs a naive loop, ``topk_mask``
invariants, GAE vs a numpy recurrence, Polyak sync algebra, masked whitening,
and ILQL loss finiteness over arbitrary shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# optional dev dependency (pyproject [dev] extra): without the guard this
# module fails COLLECTION and tier-1 needs --continue-on-collection-errors
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from trlx_tpu.models.ilql import ILQLConfig, batched_index_select, topk_mask
from trlx_tpu.models.ppo import PPOConfig
from trlx_tpu.utils.stats import whiten

_shapes = st.tuples(
    st.integers(1, 5),  # batch
    st.integers(1, 12),  # length
    st.integers(1, 7),  # feature
)


@settings(max_examples=25, deadline=None)
@given(_shapes, st.data())
def test_batched_index_select_matches_loop(shape, data):
    B, T, F = shape
    rng = np.random.RandomState(0)
    x = rng.randn(B, T, F).astype(np.float32)
    n_idx = data.draw(st.integers(1, T))
    idxs = np.stack(
        [rng.randint(0, T, size=n_idx) for _ in range(B)]
    ).astype(np.int32)
    got = np.asarray(batched_index_select(jnp.asarray(x), jnp.asarray(idxs)))
    want = np.stack([x[b][idxs[b]] for b in range(B)])
    np.testing.assert_allclose(got, want)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 6), st.integers(2, 30), st.integers(1, 30))
def test_topk_mask_keeps_exactly_topk(B, V, k):
    rng = np.random.RandomState(1)
    # distinct values: ties would make "exactly k" ambiguous
    xs = rng.permutation(B * V).reshape(B, V).astype(np.float32)
    out = np.asarray(topk_mask(jnp.asarray(xs), k))
    kept = np.isfinite(out) & (out > -1e9)
    assert (kept.sum(axis=1) == min(k, V)).all()
    for b in range(B):
        thresh = np.sort(xs[b])[-min(k, V)]
        np.testing.assert_array_equal(kept[b], xs[b] >= thresh)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 4), st.integers(1, 16), st.floats(0.8, 1.0), st.floats(0.8, 1.0))
def test_gae_matches_numpy_recurrence(B, T, gamma, lam):
    rng = np.random.RandomState(2)
    values = rng.randn(B, T).astype(np.float32)
    rewards = rng.randn(B, T).astype(np.float32)
    mask = np.ones((B, T), np.float32)
    method = PPOConfig.from_dict({"gamma": gamma, "lam": lam})
    adv, ret = method.get_advantages_and_returns(
        jnp.asarray(values), jnp.asarray(rewards), jnp.asarray(mask), use_whitening=False
    )
    # naive reverse recurrence (reference modeling_ppo.py:134-170)
    want = np.zeros((B, T), np.float32)
    last = np.zeros(B, np.float32)
    for t in reversed(range(T)):
        next_v = values[:, t + 1] if t < T - 1 else 0.0
        delta = rewards[:, t] + gamma * next_v - values[:, t]
        last = delta + gamma * lam * last
        want[:, t] = last
    np.testing.assert_allclose(np.asarray(adv), want, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(ret), want + values, atol=2e-4, rtol=2e-4)


@settings(max_examples=20, deadline=None)
@given(st.floats(0.0, 1.0))
def test_polyak_sync_algebra(alpha):
    from trlx_tpu.models.heads import sync_target_q_params

    rng = np.random.RandomState(3)
    params = {
        "ilql_heads": {
            "q_head_0": {"w": jnp.asarray(rng.randn(4, 4), jnp.float32)},
            "target_q_head_0": {"w": jnp.asarray(rng.randn(4, 4), jnp.float32)},
        }
    }
    out = sync_target_q_params(params, alpha=alpha)
    want = alpha * np.asarray(params["ilql_heads"]["q_head_0"]["w"]) + (
        1 - alpha
    ) * np.asarray(params["ilql_heads"]["target_q_head_0"]["w"])
    np.testing.assert_allclose(
        np.asarray(out["ilql_heads"]["target_q_head_0"]["w"]), want, atol=1e-6
    )
    # q heads themselves never move
    np.testing.assert_array_equal(
        np.asarray(out["ilql_heads"]["q_head_0"]["w"]),
        np.asarray(params["ilql_heads"]["q_head_0"]["w"]),
    )


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 6), st.integers(2, 20))
def test_whiten_masked_moments(B, T):
    rng = np.random.RandomState(4)
    xs = rng.randn(B, T).astype(np.float32) * 3 + 5
    mask = (rng.rand(B, T) > 0.3).astype(np.float32)
    if mask.sum() < 2:
        mask[0, :2] = 1.0
    out = np.asarray(whiten(jnp.asarray(xs), jnp.asarray(mask), shift_mean=True))
    sel = out[mask > 0]
    assert abs(sel.mean()) < 1e-2
    # whiten divides by the unbiased std (reference torch.var_mean semantics,
    # pinned by tests/test_parity_golden.py) — compare with ddof=1
    assert abs(sel.var(ddof=1) - 1.0) < 5e-2


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.integers(2, 8), st.integers(2, 9), st.booleans())
def test_ilql_loss_finite_over_shapes(B, A, V, two_qs):
    """ILQL loss never produces NaN/inf over arbitrary shapes/indices
    (reference 'loss-doesn't-crash' hypothesis test)."""
    rng = np.random.RandomState(5)
    n_q = 2 if two_qs else 1
    S = A + 1
    method = ILQLConfig.from_dict({"two_qs": two_qs})
    qs = tuple(jnp.asarray(rng.randn(B, A, V), jnp.float32) for _ in range(n_q))
    target_qs = tuple(jnp.asarray(rng.randn(B, A, V), jnp.float32) for _ in range(n_q))
    vs = jnp.asarray(rng.randn(B, S, 1), jnp.float32)
    logits = jnp.asarray(rng.randn(B, A, V), jnp.float32)
    actions = jnp.asarray(rng.randint(0, V, (B, A)), jnp.int32)
    rewards = jnp.asarray(rng.randn(B, A), jnp.float32)
    dones = jnp.asarray(rng.randint(0, 2, (B, S)), jnp.int32).at[:, 0].set(1)
    loss, stats = method.loss(
        logits=logits, qs=qs, target_qs=target_qs, vs=vs,
        actions=actions, rewards=rewards, dones=dones,
    )
    assert np.isfinite(float(loss))


@given(
    groups=st.integers(min_value=1, max_value=5),
    group_size=st.integers(min_value=2, max_value=8),
    seed=st.integers(min_value=0, max_value=2**16),
)
@settings(max_examples=30, deadline=None)
def test_group_advantages_invariants(groups, group_size, seed):
    """GRPO group advantages: zero-mean per group, scale-invariant under
    per-group reward shifts, and std-normalized when scaled."""
    from trlx_tpu.models.grpo import group_advantages_np

    rng = np.random.RandomState(seed)
    scores = rng.randn(groups * group_size).astype(np.float32) * 3.0

    def atol(rewards):
        # float32 centring leaves about eps * |rewards| in each element, and
        # the division by the group's std multiplies that by 1 / std: a group
        # whose rewards nearly coincide has advantages known that much less
        # well. So the tolerance follows each group's |rewards|.max() / std;
        # a constant is one that such a draw exceeds.
        r = rewards.reshape(groups, group_size)
        ratio = np.abs(r).max(axis=1, keepdims=True) / (r.std(axis=1, keepdims=True) + 1e-6)
        return 1e-5 * np.maximum(1.0, ratio)

    adv = group_advantages_np(scores, group_size)
    g = adv.reshape(groups, group_size)
    assert (np.abs(g.mean(axis=1, keepdims=True)) <= atol(scores)).all()
    # shifting any group's rewards by a constant leaves advantages unchanged
    shifted = scores + np.repeat(rng.randn(groups).astype(np.float32) * 10, group_size)
    moved = group_advantages_np(shifted, group_size).reshape(groups, group_size) - g
    assert (np.abs(moved) <= atol(scores) + atol(shifted)).all()
    # unscaled variant is exactly the centered rewards
    centered = group_advantages_np(scores, group_size, scale=False)
    np.testing.assert_allclose(
        centered.reshape(groups, group_size),
        scores.reshape(groups, group_size) - scores.reshape(groups, group_size).mean(axis=1, keepdims=True),
        atol=1e-5,
    )


@given(
    batch=st.integers(min_value=1, max_value=8),
    beta=st.floats(min_value=0.05, max_value=1.0),
    seed=st.integers(min_value=0, max_value=2**16),
)
@settings(max_examples=30, deadline=None)
def test_dpo_loss_invariants(batch, beta, seed):
    """DPO loss: invariant to adding a constant to both policy and reference
    logprobs of the same completion (only margins matter), bounded below by
    0, and equal to log 2 at zero margin."""
    import jax.numpy as jnp

    from trlx_tpu.models.dpo import DPOConfig

    cfg = DPOConfig(name="DPOConfig", beta=float(beta))
    rng = np.random.RandomState(seed)
    pc, pr, rc_, rr = (jnp.asarray(rng.uniform(-30, -5, batch), jnp.float32) for _ in range(4))
    loss, stats = cfg.loss(pc, pr, rc_, rr)
    assert float(loss) > 0.0
    # shift chosen logps of policy AND reference by the same constant
    c = jnp.asarray(rng.randn(batch), jnp.float32)
    loss2, _ = cfg.loss(pc + c, pr, rc_ + c, rr)
    np.testing.assert_allclose(float(loss), float(loss2), rtol=1e-4)
    # zero margin exactly
    loss0, _ = cfg.loss(pc, pc, pc, pc)
    np.testing.assert_allclose(float(loss0), np.log(2.0), rtol=1e-5)


# ---------------------------------------------------------------------------
# MoE dispatch invariants (MoEMLP, GShard einsum dispatch)
# ---------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(
    st.integers(1, 3),  # batch
    st.integers(2, 12),  # tokens
    st.integers(1, 4),  # experts
    st.integers(1, 2),  # top-k (clamped to experts)
    st.sampled_from([0.25, 1.0, 8.0]),  # capacity factor
    st.integers(0, 6),  # group size (0 = whole sequence)
    st.integers(0, 3),  # trailing padding tokens
)
def test_moe_dispatch_invariants(B, T, E, K, cf, G, pad):
    """Over arbitrary shapes/capacities/groupings/padding: outputs stay
    finite, padding rows emit exactly zero, and the balance loss stays
    within its algebraic bounds [0, E]. (Drop-free ample-capacity behavior
    is covered separately by tests/test_moe.py's group-size invariance and
    one-expert equivalence tests.)"""
    from trlx_tpu.models.transformer import (
        MoEMLP,
        TransformerConfig,
        router_aux_summary,
    )

    K = min(K, E)
    pad = min(pad, T - 1)
    cfg = TransformerConfig.mixtral(
        "test",
        dtype=jnp.float32,
        param_dtype=jnp.float32,
        num_experts=E,
        num_experts_per_tok=K,
        moe_capacity_factor=cf,
        moe_group_size=G,
    )
    rs = np.random.RandomState(B * 1000 + T * 100 + E * 10 + K)
    x = jnp.asarray(rs.randn(B, T, cfg.hidden_size), jnp.float32)
    mask = np.ones((B, T), np.float32)
    if pad:
        mask[:, T - pad :] = 0.0
    mask = jnp.asarray(mask)

    m = MoEMLP(cfg)
    params = m.init(jax.random.PRNGKey(0), x)["params"]
    y, aux = m.apply({"params": params}, x, mask)

    assert np.all(np.isfinite(np.asarray(y)))
    if pad:
        assert np.all(np.asarray(y)[:, T - pad :] == 0.0)
    lb, z = np.asarray(router_aux_summary(aux))
    # Switch balance loss: E·Σ f·p with Σf = Σp = 1 ⇒ bounds [1·(uniform), E]
    assert 0.0 <= lb <= E + 1e-4, lb
    assert z >= 0.0
