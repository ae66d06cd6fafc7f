"""Speculative decoding (draft-and-verify rollout generation).

Beyond the reference, whose generation loop is plain HF ``generate``
(SURVEY.md §3.2). Exactness contract of
``trlx_tpu/ops/speculative.py::generate_speculative``:

- greedy output is bit-identical to the plain sampler for ANY draft;
- draft == target accepts (nearly) every proposal;
- sampling remains distribution-exact (rejection-sampling identity);
- logprobs/values carry the plain sampler's PPO semantics.
"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.data.configs import ModelConfig
from trlx_tpu.models.builder import build_causal_lm
from trlx_tpu.models.transformer import make_kv_cache
from trlx_tpu.ops.sampling import GenerationConfig, generate
from trlx_tpu.ops.speculative import generate_speculative


def _models(draft_seed=1):
    kw = dict(model_extra_kwargs=dict(dtype=jnp.float32, param_dtype=jnp.float32))
    t_mod, t_params, t_cfg = build_causal_lm(
        ModelConfig("builtin:gpt2-test", **kw), head="value"
    )
    d_mod, d_params, d_cfg = build_causal_lm(
        ModelConfig("builtin:gpt2-test", **kw), head=None, seed=draft_seed
    )
    t_apply = lambda p, i, **k: t_mod.apply({"params": p}, i, **k)
    d_apply = lambda p, i, **k: d_mod.apply({"params": p}, i, **k)
    return (t_apply, t_params, t_cfg), (d_apply, d_params, d_cfg)


def _prompts(B=3, P=8, vocab=250):
    rs = np.random.RandomState(0)
    ids = rs.randint(0, vocab, (B, P)).astype(np.int32)
    mask = np.ones((B, P), np.int32)
    mask[0, :3] = 0
    if B > 2:
        mask[2, :5] = 0
    ids[mask == 0] = 258
    return jnp.asarray(ids), jnp.asarray(mask)


def _spec(t, d, ids, mask, cfg, gamma, rng=0, **kw):
    (t_apply, t_params, t_cfg), (d_apply, d_params, d_cfg) = t, d
    return generate_speculative(
        t_apply, t_params, d_apply, d_params,
        lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
        lambda b, s: make_kv_cache(d_cfg, b, s, jnp.float32),
        ids, mask, jax.random.PRNGKey(rng), cfg, gamma=gamma, **kw,
    )


@pytest.mark.parametrize("gamma", [1, 3, 5])
def test_greedy_exactly_matches_plain_sampler(gamma):
    """For any draft, greedy speculative output (tokens, mask, logprobs,
    values) equals the plain sampler's greedy decode."""
    t, d = _models(draft_seed=1)  # draft is a DIFFERENT random model
    ids, mask = _prompts()
    cfg = GenerationConfig(
        max_new_tokens=10, do_sample=False, eos_token_id=None, pad_token_id=258
    )
    t_apply, t_params, t_cfg = t
    ref = generate(
        t_apply, t_params, lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
        ids, mask, jax.random.PRNGKey(0), cfg,
    )
    out = jax.jit(
        partial(_spec, t, d, cfg=cfg, gamma=gamma)
    )(ids, mask)
    assert (np.asarray(out.response_tokens) == np.asarray(ref.response_tokens)).all()
    assert (np.asarray(out.response_mask) == np.asarray(ref.response_mask)).all()
    np.testing.assert_allclose(
        np.asarray(out.response_logprobs), np.asarray(ref.response_logprobs), atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(out.response_values), np.asarray(ref.response_values), atol=1e-5
    )


def test_greedy_eos_early_stop_matches():
    t, d = _models()
    ids, mask = _prompts()
    t_apply, t_params, t_cfg = t
    base = generate(
        t_apply, t_params, lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
        ids, mask, jax.random.PRNGKey(0),
        GenerationConfig(max_new_tokens=10, do_sample=False, eos_token_id=None, pad_token_id=258),
    )
    # declare the token row 0 greedily emits at step 2 as eos → early stop
    eos = int(np.asarray(base.response_tokens)[0, 2])
    cfg = GenerationConfig(
        max_new_tokens=10, do_sample=False, eos_token_id=eos, pad_token_id=258
    )
    ref = generate(
        t_apply, t_params, lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
        ids, mask, jax.random.PRNGKey(0), cfg,
    )
    out = _spec(t, d, ids, mask, cfg, gamma=3)
    assert (np.asarray(out.response_tokens) == np.asarray(ref.response_tokens)).all()
    assert (np.asarray(out.response_mask) == np.asarray(ref.response_mask)).all()


def test_identical_draft_accepts_everything():
    """Draft == target (same backbone params): the acceptance rate must be
    ~1 and the round count collapses to ~N/(gamma+1)."""
    t, _ = _models()
    t_apply, t_params, t_cfg = t
    # headless apply over the same backbone params as the target policy
    from trlx_tpu.models.transformer import CausalTransformer

    bare = CausalTransformer(t_cfg)
    d = (lambda p, i, **k: bare.apply({"params": p}, i, **k), t_params["backbone"], t_cfg)
    ids, mask = _prompts()
    cfg = GenerationConfig(
        max_new_tokens=12, do_sample=True, temperature=1.0, eos_token_id=None,
        pad_token_id=258,
    )
    out, stats = _spec(t, d, ids, mask, cfg, gamma=4, return_stats=True)
    assert np.asarray(out.response_mask).all()
    rate = float(stats["acceptance_rate"])
    rounds = int(stats["rounds"])
    assert rate > 0.95, rate
    # full acceptance commits gamma+1 = 5 per round → ~3 rounds for N=12
    assert rounds <= 5, rounds


def test_identical_draft_greedy_minimal_rounds():
    """Greedy + draft == target: every round fully accepts, so generation
    takes exactly ceil(N/(gamma+1)) rounds. Catches any draft-cache
    corruption across rounds (e.g. a missing d_G K/V write after a fully
    accepted round) as extra rejection rounds."""
    t, _ = _models()
    t_apply, t_params, t_cfg = t
    from trlx_tpu.models.transformer import CausalTransformer

    bare = CausalTransformer(t_cfg)
    d = (lambda p, i, **k: bare.apply({"params": p}, i, **k), t_params["backbone"], t_cfg)
    ids, mask = _prompts()
    N, G = 24, 3
    cfg = GenerationConfig(
        max_new_tokens=N, do_sample=False, eos_token_id=None, pad_token_id=258
    )
    out, stats = _spec(t, d, ids, mask, cfg, gamma=G, return_stats=True)
    assert np.asarray(out.response_mask).all()
    assert int(stats["rounds"]) == -(-N // (G + 1)), int(stats["rounds"])


def test_sampling_first_token_distribution_matches_target():
    """Distribution exactness smoke: over many rows of the same prompt, the
    speculative first token's empirical distribution matches the plain
    target sampler's (total variation within sampling noise)."""
    t, d = _models(draft_seed=7)
    B = 512
    ids = jnp.tile(jnp.asarray([[5, 9, 17, 23]], jnp.int32), (B, 1))
    mask = jnp.ones((B, 4), jnp.int32)
    cfg = GenerationConfig(
        max_new_tokens=2, do_sample=True, temperature=1.0, top_k=4,
        eos_token_id=None, pad_token_id=258,
    )
    t_apply, t_params, t_cfg = t
    ref = generate(
        t_apply, t_params, lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
        ids, mask, jax.random.PRNGKey(3), cfg,
    )
    out = _spec(t, d, ids, mask, cfg, gamma=2, rng=11)
    a = np.bincount(np.asarray(ref.response_tokens)[:, 0], minlength=259) / B
    b = np.bincount(np.asarray(out.response_tokens)[:, 0], minlength=259) / B
    tv = 0.5 * np.abs(a - b).sum()
    assert tv < 0.15, tv  # top_k=4, n=512 → noise floor ≈ 0.06


def test_cross_family_draft_greedy_exact():
    """The draft can be a DIFFERENT architecture family (the practical case:
    a small distilled draft) — only the vocab must match. Greedy parity must
    still be bit-exact."""
    kw = dict(model_extra_kwargs=dict(dtype=jnp.float32, param_dtype=jnp.float32))
    t_mod, t_params, t_cfg = build_causal_lm(
        ModelConfig("builtin:gpt2-test", **kw), head="value"
    )
    # llama-test: rotary + RMSNorm + GQA — nothing like gpt2, same 259 vocab
    d_mod, d_params, d_cfg = build_causal_lm(
        ModelConfig("builtin:llama-test", **kw), head=None, seed=5
    )
    assert d_cfg.vocab_size == t_cfg.vocab_size
    t = (lambda p, i, **k: t_mod.apply({"params": p}, i, **k), t_params, t_cfg)
    d = (lambda p, i, **k: d_mod.apply({"params": p}, i, **k), d_params, d_cfg)
    ids, mask = _prompts()
    cfg = GenerationConfig(
        max_new_tokens=8, do_sample=False, eos_token_id=None, pad_token_id=258
    )
    t_apply, t_params, t_cfg = t
    ref = generate(
        t_apply, t_params, lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
        ids, mask, jax.random.PRNGKey(0), cfg,
    )
    out = _spec(t, d, ids, mask, cfg, gamma=3)
    assert (np.asarray(out.response_tokens) == np.asarray(ref.response_tokens)).all()


def test_grpo_rollouts_ride_speculative_sampler(tmp_path):
    """GRPO inherits the speculative sampler through the shared generate
    path: acceptance stats land in its make_experience stats."""
    import trlx_tpu.trainer.grpo  # noqa: F401
    import trlx_tpu.pipeline.offline_pipeline  # noqa: F401
    from trlx_tpu.data.default_configs import default_grpo_config
    from trlx_tpu.pipeline import get_pipeline
    from trlx_tpu.trainer import get_trainer

    config = default_grpo_config().evolve(
        train=dict(
            seq_length=24, batch_size=8, total_steps=2, eval_interval=10**6,
            checkpoint_interval=10**6, save_best=False, tracker=None,
            checkpoint_dir=str(tmp_path / "ckpt"),
        ),
        model=dict(
            model_path="builtin:gpt2-test",
            draft_model_path="builtin:gpt2-test",
            draft_gamma=2,
        ),
        method=dict(
            num_rollouts=8, chunk_size=8, group_size=4, ppo_epochs=1,
            gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0, do_sample=True),
        ),
    )
    trainer = get_trainer(config.train.trainer)(
        config=config,
        reward_fn=lambda samples, prompts, outputs, **kw: [float(len(o)) for o in outputs],
        metric_fn=None, stop_sequences=[],
    )
    pipeline = get_pipeline(config.train.pipeline)(
        ["hello", "world"] * 2, 12, trainer.tokenizer
    )
    trainer.add_prompt_pipeline(pipeline)
    trainer.make_experience(8)
    assert "rollout/spec_acceptance_rate" in trainer.make_experience_stats


def test_acceptance_rule_is_distribution_exact():
    """The committed-token marginal of the rejection-sampling rule IS the
    target distribution — checked against arbitrary enumerated p/q over a
    tiny vocab, no models involved.

    For gamma=1 the first committed token x_0 = d_1 if accepted else the
    residual resample; the scheme guarantees P(x_0 = t) = p_0(t) exactly.
    Monte-Carlo over the pure rule with d_1 ~ q_1 must match p_0 within
    binomial noise."""
    from trlx_tpu.ops.speculative import accept_and_extra

    V, N = 5, 40_000
    rs = np.random.RandomState(0)
    # arbitrary, deliberately mismatched distributions (incl. a zero in p)
    p0 = np.asarray([0.5, 0.0, 0.2, 0.25, 0.05])
    p1 = np.ones(V) / V  # bonus dist (irrelevant to x_0's marginal)
    q1 = np.asarray([0.1, 0.4, 0.1, 0.15, 0.25])

    p_probs = jnp.broadcast_to(jnp.asarray(np.stack([p0, p1]), jnp.float32), (N, 2, V))
    q_probs = jnp.broadcast_to(jnp.asarray(q1[None], jnp.float32), (N, 1, V))
    d_toks = jnp.asarray(rs.choice(V, size=(N, 1), p=q1), jnp.int32)

    k, extra, _ = jax.jit(accept_and_extra, static_argnums=(4,))(
        p_probs, q_probs, d_toks, jax.random.PRNGKey(1), True
    )
    k, extra, d = np.asarray(k), np.asarray(extra), np.asarray(d_toks)[:, 0]
    x0 = np.where(k >= 1, d, extra)
    freq = np.bincount(x0, minlength=V) / N
    # 4-sigma binomial bound per bucket
    bound = 4 * np.sqrt(np.maximum(p0 * (1 - p0), 1e-4) / N)
    assert np.all(np.abs(freq - p0) <= bound), (freq, p0, bound)
    # the zero-probability target token must NEVER be committed as x_0
    assert freq[1] == 0.0, freq


def test_transition_mask_composes_losslessly():
    """A prev→next transition mask (the trainer logit_mask, e.g.
    randomwalks) applies to draft AND target: greedy masked speculative
    output equals the plain sampler with the equivalent adjust hook, and
    sampled tokens always obey the mask."""
    from trlx_tpu.ops.sampling import apply_transition_mask

    t, d = _models(draft_seed=3)
    t_apply, t_params, t_cfg = t
    ids, mask = _prompts()
    # ring transitions over a 64-token sub-vocab: token v -> {v+1, v+2} mod 64
    V = 64
    tmask = np.zeros((V, V), bool)
    for v in range(V):
        tmask[v, (v + 1) % V] = True
        tmask[v, (v + 2) % V] = True
    tmask_j = jnp.asarray(tmask)

    cfg = GenerationConfig(
        max_new_tokens=10, do_sample=False, eos_token_id=None, pad_token_id=258
    )

    def adjust(step_out, logits):
        return apply_transition_mask(tmask_j, step_out["last_tokens"], logits)

    ref = generate(
        t_apply, t_params, lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
        ids, mask, jax.random.PRNGKey(0), cfg, adjust_logits=adjust,
    )
    out = _spec(t, d, ids, mask, cfg, gamma=3, transition_mask=tmask_j)
    assert (np.asarray(out.response_tokens) == np.asarray(ref.response_tokens)).all()
    np.testing.assert_allclose(
        np.asarray(out.response_logprobs), np.asarray(ref.response_logprobs), atol=1e-5
    )

    # sampled mode: every committed transition must be mask-legal
    cfg_s = GenerationConfig(
        max_new_tokens=10, do_sample=True, eos_token_id=None, pad_token_id=258
    )
    outs = _spec(t, d, ids, mask, cfg_s, gamma=3, rng=5, transition_mask=tmask_j)
    toks = np.asarray(outs.response_tokens)
    msk = np.asarray(outs.response_mask)
    prev = np.asarray(ids)[:, -1]
    for b in range(toks.shape[0]):
        p = prev[b]
        for j in range(toks.shape[1]):
            if not msk[b, j]:
                break
            nxt = toks[b, j]
            if 0 <= p < V:  # unknown rows sample unconstrained by design
                assert tmask[p, nxt], (b, j, p, nxt)
            p = nxt


def test_trainer_logit_mask_rides_speculative_sampler(tmp_path):
    """Trainer-level logit_mask + draft model: the speculative sampler IS
    used (acceptance stats recorded) and every sampled transition obeys the
    mask — mask-only adjustment no longer forces the plain-sampler
    fallback."""
    import trlx_tpu.trainer.ppo  # noqa: F401
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.trainer import get_trainer

    V = 8
    tmask = np.zeros((V, V), bool)
    for t in range(V):
        tmask[t, (t + 1) % V] = True  # only t -> (t+1) % 8

    config = default_ppo_config().evolve(
        train=dict(
            seq_length=16, batch_size=4, total_steps=2, eval_interval=10**6,
            checkpoint_interval=10**6, save_best=False, tracker=None,
            checkpoint_dir=str(tmp_path / "ckpt"),
        ),
        model=dict(
            model_path="builtin:gpt2-test",
            num_layers_unfrozen=1,
            draft_model_path="builtin:gpt2-test",
            draft_gamma=3,
        ),
        method=dict(
            num_rollouts=4, chunk_size=4, ppo_epochs=1,
            gen_kwargs=dict(max_new_tokens=6, top_k=0, top_p=1.0, do_sample=True),
        ),
    )
    trainer = get_trainer(config.train.trainer)(
        config=config,
        reward_fn=lambda samples, prompts, outputs, **kw: [0.0] * len(outputs),
        metric_fn=None, stop_sequences=[], logit_mask=tmask,
    )
    prompts = np.asarray([[2], [5], [7], [1]], np.int32)
    out = trainer.generate(prompts, np.ones_like(prompts))
    assert trainer.last_spec_stats, "speculative sampler did not run"
    toks = np.asarray(out.response_tokens)
    resp_mask = np.asarray(out.response_mask)
    for b in range(toks.shape[0]):
        last = prompts[b, -1]
        for j in range(toks.shape[1]):
            if not resp_mask[b, j]:
                break
            assert toks[b, j] == (last + 1) % V, (b, j, toks[b])
            last = toks[b, j]


def test_trainer_speculative_rollouts_e2e(tmp_path):
    """PPO make_experience + learn with a draft model configured: the
    speculative sampler slots in transparently (same GenerationOutput
    contract) and training runs."""
    import trlx_tpu.trainer.ppo  # noqa: F401
    import trlx_tpu.pipeline.offline_pipeline  # noqa: F401
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.pipeline import get_pipeline
    from trlx_tpu.trainer import get_trainer

    config = default_ppo_config().evolve(
        train=dict(
            seq_length=24, batch_size=8, total_steps=2, eval_interval=2,
            checkpoint_interval=10**6, save_best=False, tracker=None,
            checkpoint_dir=str(tmp_path / "ckpt"),
        ),
        model=dict(
            model_path="builtin:gpt2-test",
            num_layers_unfrozen=1,
            draft_model_path="builtin:gpt2-test",
            draft_gamma=3,
        ),
        method=dict(
            num_rollouts=8, chunk_size=8, ppo_epochs=1,
            gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0, do_sample=True),
        ),
    )
    trainer = get_trainer(config.train.trainer)(
        config=config,
        reward_fn=lambda samples, prompts, outputs, **kw: [float(len(o)) for o in outputs],
        metric_fn=None,
        stop_sequences=[],
    )
    assert trainer.draft_module is not None
    pipeline = get_pipeline(config.train.pipeline)(
        ["hello world", "foo", "bar baz", "qux"] * 2, 12, trainer.tokenizer
    )
    trainer.add_prompt_pipeline(pipeline)
    trainer.make_experience(8)
    assert len(trainer.store) == 8
    assert 0.0 <= trainer.make_experience_stats["rollout/spec_acceptance_rate"] <= 1.0
    trainer.prepare_learning()
    stats = trainer.train_step(next(iter(trainer.store.create_loader(8, shuffle=True))))
    assert np.isfinite(float(np.asarray(stats["losses/total_loss"])))


@pytest.mark.parametrize("gamma", [1, 3])
def test_greedy_min_new_tokens_matches_plain_sampler(gamma):
    """min_new_tokens composes losslessly (round-4: previously an explicit
    plain-sampler fallback): greedy speculative output with per-row eos
    blocking is bit-identical to the plain sampler's, for any draft."""
    t, d = _models(draft_seed=1)
    ids, mask = _prompts()
    t_apply, t_params, t_cfg = t
    base = generate(
        t_apply, t_params, lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
        ids, mask, jax.random.PRNGKey(0),
        GenerationConfig(max_new_tokens=10, do_sample=False, eos_token_id=None, pad_token_id=258),
    )
    # an eos that greedy row 0 would emit early — min_new_tokens must defer it
    eos = int(np.asarray(base.response_tokens)[0, 2])
    cfg = GenerationConfig(
        max_new_tokens=10, do_sample=False, eos_token_id=eos, pad_token_id=258,
        min_new_tokens=6,
    )
    ref = generate(
        t_apply, t_params, lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
        ids, mask, jax.random.PRNGKey(0), cfg,
    )
    out = _spec(t, d, ids, mask, cfg, gamma=gamma)
    assert (np.asarray(out.response_tokens) == np.asarray(ref.response_tokens)).all()
    assert (np.asarray(out.response_mask) == np.asarray(ref.response_mask)).all()
    np.testing.assert_allclose(
        np.asarray(out.response_logprobs), np.asarray(ref.response_logprobs), atol=1e-5
    )


def test_sampled_min_new_tokens_blocks_eos():
    """Sampled path: no generated row may contain eos before min_new_tokens
    (positions are per row — later rounds start mid-response)."""
    t, d = _models(draft_seed=1)
    ids, mask = _prompts()
    cfg = GenerationConfig(
        max_new_tokens=10, do_sample=True, eos_token_id=7, pad_token_id=258,
        min_new_tokens=5, top_k=0, top_p=1.0,
    )
    for seed in range(4):
        out = _spec(t, d, ids, mask, cfg, gamma=3, rng=seed)
        toks = np.asarray(out.response_tokens)
        m = np.asarray(out.response_mask)
        gen_count = m.sum(axis=1)
        for b in range(toks.shape[0]):
            before_min = toks[b, : min(5, int(gen_count[b]))]
            assert (before_min != 7).all(), (b, toks[b], m[b])


def _ilql_models(draft_seed=1):
    kw = dict(model_extra_kwargs=dict(dtype=jnp.float32, param_dtype=jnp.float32))
    t_mod, t_params, t_cfg = build_causal_lm(
        ModelConfig("builtin:gpt2-test", **kw), head="ilql"
    )
    d_mod, d_params, d_cfg = build_causal_lm(
        ModelConfig("builtin:gpt2-test", **kw), head=None, seed=draft_seed
    )
    t_apply = lambda p, i, **k: t_mod.apply({"params": p}, i, **k)
    d_apply = lambda p, i, **k: d_mod.apply({"params": p}, i, **k)
    return (t_apply, t_params, t_cfg), (d_apply, d_params, d_cfg)


def _ilql_adjust(beta=1.0):
    """The trainer's ILQL reshaping (trainer/ilql.py::adjust_logits_fn),
    leading-dim polymorphic as the speculative contract requires."""

    def adjust(step_out, logits):
        tq = step_out["target_qs"]
        q = jnp.minimum(tq[0], tq[1]) if isinstance(tq, (tuple, list)) else tq
        adv = q.astype(jnp.float32) - step_out["vs"].astype(jnp.float32)
        return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1) + beta * adv

    return adjust


@pytest.mark.parametrize("gamma", [1, 3])
def test_greedy_ilql_adjust_matches_plain_sampler(gamma):
    """Round-4: the algo adjust hook (ILQL Q-value reshaping) now composes
    with speculative decoding — greedy output through the reshaped target
    distribution is bit-identical to the plain sampler's, for a plain
    (headless, mismatched) draft."""
    t, d = _ilql_models(draft_seed=1)
    ids, mask = _prompts()
    cfg = GenerationConfig(
        max_new_tokens=8, do_sample=False, eos_token_id=None, pad_token_id=258
    )
    t_apply, t_params, t_cfg = t
    adjust = _ilql_adjust(beta=2.0)
    ref = generate(
        t_apply, t_params, lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
        ids, mask, jax.random.PRNGKey(0), cfg, adjust_logits=adjust,
    )
    out = _spec(t, d, ids, mask, cfg, gamma=gamma, adjust_logits=adjust)
    assert (np.asarray(out.response_tokens) == np.asarray(ref.response_tokens)).all()
    np.testing.assert_allclose(
        np.asarray(out.response_logprobs), np.asarray(ref.response_logprobs), atol=1e-5
    )


def test_greedy_strong_adjust_changes_and_matches():
    """A hook with a decisive effect (logit reversal, consuming a step_out
    field): speculative output must track the ADJUSTED distribution — it
    differs from the unadjusted decode and matches the adjusted plain
    sampler exactly."""
    t, d = _ilql_models(draft_seed=1)
    ids, mask = _prompts()
    cfg = GenerationConfig(
        max_new_tokens=8, do_sample=False, eos_token_id=None, pad_token_id=258
    )
    t_apply, t_params, t_cfg = t

    def reverse(step_out, logits):
        # consumes a per-position head output, so the step_out plumbing is
        # load-bearing; 0.0 * vs keeps shapes honest without changing math
        return -logits + 0.0 * step_out["vs"].astype(jnp.float32)

    ref = generate(
        t_apply, t_params, lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
        ids, mask, jax.random.PRNGKey(0), cfg, adjust_logits=reverse,
    )
    plain = generate(
        t_apply, t_params, lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
        ids, mask, jax.random.PRNGKey(0), cfg,
    )
    assert (np.asarray(plain.response_tokens) != np.asarray(ref.response_tokens)).any()
    out = _spec(t, d, ids, mask, cfg, gamma=3, adjust_logits=reverse)
    assert (np.asarray(out.response_tokens) == np.asarray(ref.response_tokens)).all()


@pytest.mark.slow
def test_sampled_adjust_distribution_matches_target():
    """Sampled-mode exactness for the adjusted path: the speculative first
    token's empirical distribution matches the plain sampler's under the
    SAME adjust hook (total variation within sampling noise)."""
    t, d = _ilql_models(draft_seed=7)
    B = 512
    ids = jnp.tile(jnp.asarray([[5, 9, 17, 23]], jnp.int32), (B, 1))
    mask = jnp.ones((B, 4), jnp.int32)
    cfg = GenerationConfig(
        max_new_tokens=2, do_sample=True, temperature=1.0, top_k=4,
        eos_token_id=None, pad_token_id=258,
    )
    t_apply, t_params, t_cfg = t
    adjust = _ilql_adjust(beta=3.0)
    ref = generate(
        t_apply, t_params, lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
        ids, mask, jax.random.PRNGKey(3), cfg, adjust_logits=adjust,
    )
    out = _spec(t, d, ids, mask, cfg, gamma=2, rng=11, adjust_logits=adjust)
    a = np.bincount(np.asarray(ref.response_tokens)[:, 0], minlength=259) / B
    b = np.bincount(np.asarray(out.response_tokens)[:, 0], minlength=259) / B
    tv = 0.5 * np.abs(a - b).sum()
    assert tv < 0.15, tv  # top_k=4, n=512 -> noise floor ~= 0.06


@pytest.mark.slow
def test_all_sampler_features_compose_greedy_exact():
    """The full composition — transition mask + min_new_tokens + algo
    adjust hook + eos — in ONE speculative decode, bit-identical to the
    plain sampler with the equivalent composed hook."""
    from trlx_tpu.ops.sampling import apply_transition_mask

    t, d = _ilql_models(draft_seed=3)
    t_apply, t_params, t_cfg = t
    ids, mask = _prompts()
    V = 64
    tmask = np.zeros((V, V), bool)
    for v in range(V):
        for step in (1, 2, 3):
            tmask[v, (v + step) % V] = True
    tmask_j = jnp.asarray(tmask)
    ilql_adjust = _ilql_adjust(beta=2.0)

    def composed(step_out, logits):
        # plain-sampler order: algo adjust, then transition mask (the eos
        # block lives inside sample_token_from_logits / the spec verify)
        logits = ilql_adjust(step_out, logits)
        return apply_transition_mask(tmask_j, step_out["last_tokens"], logits)

    # pick an eos the unconstrained composed decode emits EARLY (position <
    # min_new_tokens), so the min-block genuinely reroutes the decode and
    # eos termination genuinely fires later
    cfg0 = GenerationConfig(
        max_new_tokens=10, do_sample=False, eos_token_id=None, pad_token_id=258
    )
    base = generate(
        t_apply, t_params, lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
        ids, mask, jax.random.PRNGKey(0), cfg0, adjust_logits=composed,
    )
    eos = int(np.asarray(base.response_tokens)[0, 1])

    def run(min_new):
        cfg = GenerationConfig(
            max_new_tokens=10, do_sample=False, eos_token_id=eos,
            pad_token_id=258, min_new_tokens=min_new,
        )
        ref = generate(
            t_apply, t_params, lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
            ids, mask, jax.random.PRNGKey(0), cfg, adjust_logits=composed,
        )
        out = _spec(
            t, d, ids, mask, cfg, gamma=3,
            transition_mask=tmask_j, adjust_logits=ilql_adjust,
        )
        return ref, out

    ref, out = run(min_new=4)
    assert (np.asarray(out.response_tokens) == np.asarray(ref.response_tokens)).all()
    assert (np.asarray(out.response_mask) == np.asarray(ref.response_mask)).all()
    np.testing.assert_allclose(
        np.asarray(out.response_logprobs), np.asarray(ref.response_logprobs), atol=1e-5
    )
    # the eos/min features must be LOAD-BEARING in this composition:
    ref0, out0 = run(min_new=0)
    assert (np.asarray(out0.response_tokens) == np.asarray(ref0.response_tokens)).all()
    assert (np.asarray(ref0.response_tokens) != np.asarray(ref.response_tokens)).any(), (
        "min_new_tokens did not change the composed decode — inert test"
    )
    m0 = np.asarray(ref0.response_mask)
    assert m0[0].sum() < m0.shape[1], "eos termination never fired — inert test"


@pytest.mark.slow
@pytest.mark.parametrize(
    "par",
    [
        dict(data=2, fsdp=2, model=2),
        dict(data=1, fsdp=2, model=2, sequence=2),
        dict(pipe=2, fsdp=2, model=2),
    ],
    ids=["dp2_fsdp2_tp2", "fsdp2_tp2_sp2", "pipe2_fsdp2_tp2"],
)
def test_speculative_on_sharded_mesh(par, tmp_path):
    """Draft-and-verify rollouts over real GSPMD meshes: dp x fsdp x tp,
    fsdp x tp x sp, and pipe x fsdp x tp (scan_layers on). Same acceptance
    stats as single-device — the sampler program is mesh-agnostic. The pipe
    case exercises per-microbatch cache_index slicing through the GPipe
    schedule (the target verifies pipelined; the draft runs replicated via
    ignore_pipe_mesh) — the composition the round-4 verdict flagged as a
    self-imposed hole."""
    import trlx_tpu.trainer.ppo  # noqa: F401
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.parallel.mesh import set_global_mesh
    from trlx_tpu.trainer import get_trainer

    set_global_mesh(None)
    cfg = default_ppo_config().evolve(
        train=dict(total_steps=1, batch_size=8, seq_length=32,
                   eval_interval=10**6, checkpoint_interval=10**6,
                   tracker=None, checkpoint_dir=str(tmp_path)),
        model=dict(model_path="builtin:gpt2-test", num_layers_unfrozen=1,
                   model_extra_kwargs=dict(scan_layers=True),
                   draft_model_path="builtin:gpt2-test", draft_gamma=3),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        parallel=par,
        method=dict(num_rollouts=8, chunk_size=8,
                    gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0,
                                    do_sample=True)),
    )
    t = get_trainer(cfg.train.trainer)(cfg, reward_fn=lambda **kw: [0.0] * 8)
    ids = np.full((8, 8), 65, np.int32)
    out = t.generate(ids, np.ones_like(ids))
    m = np.asarray(jax.device_get(out.response_mask))
    assert m.sum() > 0
    assert 0.0 <= t.last_spec_stats["rollout/spec_acceptance_rate"] <= 1.0
    set_global_mesh(None)


@pytest.mark.slow
def test_pipe_mesh_greedy_matches_unpipelined(tmp_path):
    """Losslessness of the pipe x speculative composition: greedy rollouts
    from a draft-equipped trainer on a pipe2 x fsdp2 x tp2 mesh emit the
    SAME tokens as a draftless trainer on the same mesh — the speculative
    sampler through the GPipe schedule (per-microbatch cache_index slicing)
    changes nothing but wall-clock."""
    import trlx_tpu.trainer.ppo  # noqa: F401
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.parallel.mesh import set_global_mesh
    from trlx_tpu.trainer import get_trainer

    def build(draft):
        set_global_mesh(None)
        model = dict(model_path="builtin:gpt2-test", num_layers_unfrozen=1,
                     model_extra_kwargs=dict(scan_layers=True))
        if draft:
            model.update(draft_model_path="builtin:gpt2-test", draft_gamma=3,
                         draft_model_extra_kwargs=dict(num_layers=1))
        cfg = default_ppo_config().evolve(
            train=dict(total_steps=1, batch_size=8, seq_length=32,
                       eval_interval=10**6, checkpoint_interval=10**6,
                       tracker=None, checkpoint_dir=str(tmp_path / f"d{draft}")),
            model=model,
            tokenizer=dict(tokenizer_path="builtin:bytes"),
            parallel=dict(pipe=2, fsdp=2, model=2),
            method=dict(num_rollouts=8, chunk_size=8,
                        gen_kwargs=dict(max_new_tokens=8, do_sample=False)),
        )
        return get_trainer(cfg.train.trainer)(cfg, reward_fn=lambda **kw: [0.0] * 8)

    ids = np.stack([np.arange(65 + i, 73 + i) for i in range(8)]).astype(np.int32)
    mask = np.ones_like(ids)
    ref = build(draft=False).generate(ids, mask)
    spec_t = build(draft=True)
    out = spec_t.generate(ids, mask)
    assert (np.asarray(jax.device_get(out.response_tokens))
            == np.asarray(jax.device_get(ref.response_tokens))).all()
    assert (np.asarray(jax.device_get(out.response_mask))
            == np.asarray(jax.device_get(ref.response_mask))).all()
    assert 0.0 <= spec_t.last_spec_stats["rollout/spec_acceptance_rate"] <= 1.0
    set_global_mesh(None)


class TestPerRowRngComposition:
    """per_row_rng × speculative decoding (the continuous-batching
    composition seam, ROADMAP item 2's named blocker — removed): every
    rng consumer (draft proposals, acceptance uniforms, residual/bonus)
    advances a per-row key chain a fixed number of times per round, so a
    row's sample stream depends only on (its chain, its round) — batch
    composition invariance, pinned by the B=1-loop parity test."""

    def test_batched_equals_row_by_row_loop_sampled(self):
        """THE per-row contract: a sampled B=3 batch is bit-identical per
        row to running each row alone with its chain — tokens, behavior
        logprobs, values, and masks (eos + min_new_tokens active)."""
        from trlx_tpu.ops.sampling import per_row_keys

        t, d = _models()
        ids, mask = _prompts(B=3)
        cfg = GenerationConfig(
            max_new_tokens=6, pad_token_id=258, eos_token_id=5,
            min_new_tokens=1, temperature=0.9, top_k=7, per_row_rng=True,
        )
        keys = per_row_keys(jax.random.PRNGKey(0), 3)

        def run(i0, i1, k):
            (t_apply, t_params, t_cfg), (d_apply, d_params, d_cfg) = t, d
            from trlx_tpu.ops.speculative import generate_speculative

            return generate_speculative(
                t_apply, t_params, d_apply, d_params,
                lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
                lambda b, s: make_kv_cache(d_cfg, b, s, jnp.float32),
                ids[i0:i1], mask[i0:i1], k, cfg, gamma=3,
            )

        batched = run(0, 3, keys)
        for i in range(3):
            solo = run(i, i + 1, keys[i : i + 1])
            for f in (
                "response_tokens", "response_logprobs",
                "response_values", "response_mask",
            ):
                np.testing.assert_array_equal(
                    np.asarray(getattr(batched, f)[i]),
                    np.asarray(getattr(solo, f)[0]),
                    err_msg=f"row {i} {f}",
                )

    def test_single_key_entry_derives_per_row_chains(self):
        """Passing ONE key with per_row_rng derives the same chains
        per_row_keys would (the plain sampler's convention), so the two
        entry forms are interchangeable."""
        from trlx_tpu.ops.sampling import per_row_keys

        t, d = _models()
        ids, mask = _prompts(B=3)
        cfg = GenerationConfig(
            max_new_tokens=4, pad_token_id=258, eos_token_id=None,
            per_row_rng=True,
        )
        stacked = _spec(t, d, ids, mask, cfg, 2, rng=0)
        (t_apply, t_params, t_cfg), (d_apply, d_params, d_cfg) = t, d
        out = generate_speculative(
            t_apply, t_params, d_apply, d_params,
            lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
            lambda b, s: make_kv_cache(d_cfg, b, s, jnp.float32),
            ids, mask, per_row_keys(jax.random.PRNGKey(0), 3), cfg, gamma=2,
        )
        np.testing.assert_array_equal(
            np.asarray(stacked.response_tokens), np.asarray(out.response_tokens)
        )

    def test_multi_row_greedy_bit_identical(self):
        """Greedy multi-row per_row_rng (previously rejected) consumes no
        rng and stays bit-identical to the plain sampler."""
        t, d = _models()
        ids, mask = _prompts(B=3)
        cfg = GenerationConfig(
            max_new_tokens=6, do_sample=False, eos_token_id=None,
            pad_token_id=258, per_row_rng=True,
        )
        t_apply, t_params, t_cfg = t
        ref = generate(
            t_apply, t_params,
            lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
            ids, mask, jax.random.PRNGKey(0), cfg,
        )
        out = _spec(t, d, ids, mask, cfg, 3)
        assert (
            np.asarray(out.response_tokens) == np.asarray(ref.response_tokens)
        ).all()
        assert (
            np.asarray(out.response_mask) == np.asarray(ref.response_mask)
        ).all()

    def test_single_row_accepted_greedy_bit_identical(self):
        t, d = _models()
        ids, mask = _prompts(B=3)
        ids, mask = ids[:1], mask[:1]
        cfg = GenerationConfig(
            max_new_tokens=8, do_sample=False, eos_token_id=None,
            pad_token_id=258, per_row_rng=True,
        )
        t_apply, t_params, t_cfg = t
        ref = generate(
            t_apply, t_params,
            lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
            ids, mask, jax.random.PRNGKey(0), cfg,
        )
        out = _spec(t, d, ids, mask, cfg, 3)
        assert (
            np.asarray(out.response_tokens) == np.asarray(ref.response_tokens)
        ).all()
        assert (
            np.asarray(out.response_mask) == np.asarray(ref.response_mask)
        ).all()

    def test_single_row_sampled_runs(self):
        """Sampling with per_row_rng at n_rows == 1 executes (no raise) and
        produces a well-formed output — the streams differ from the plain
        sampler's by design (speculative sampling is distribution-exact,
        not stream-equal)."""
        t, d = _models()
        ids, mask = _prompts(B=3)
        cfg = GenerationConfig(
            max_new_tokens=6, pad_token_id=258, eos_token_id=None,
            per_row_rng=True,
        )
        out = _spec(t, d, ids[:1], mask[:1], cfg, 2)
        assert np.asarray(out.response_tokens).shape == (1, 6)
        assert int(np.asarray(out.response_mask).sum()) == 6


# ---------------------------------------------------------------------------
# the model's own next-token-prediction module as the drafter
# (``module_drafter``; the toy of K-EXAONE: a window of 8 on four layers, so
# every ring is shorter than the row, and a global layer)
# ---------------------------------------------------------------------------


def _self_drafting_model():
    kw = dict(model_extra_kwargs=dict(dtype=jnp.float32, param_dtype=jnp.float32, attention_impl="xla"))
    mod, params, cfg = build_causal_lm(ModelConfig("builtin:k-exaone-test", **kw), head="value")
    apply = lambda p, i, **k: mod.apply({"params": p}, i, **k)
    draft = lambda p, h, n, **k: mod.apply({"params": p}, h, n, method="draft", **k)
    return apply, draft, params, cfg


def _self_spec(model, ids, mask, cfg, rng=0, **kw):
    from trlx_tpu.ops.speculative import module_drafter

    apply, draft, params, tcfg = model
    L = tcfg.num_layers
    return generate_speculative(
        apply, params, None, params,
        lambda b, s: make_kv_cache(tcfg, b, s, jnp.float32)[:L],
        lambda b, s: make_kv_cache(tcfg, b, s, jnp.float32)[L:],
        ids, mask, jax.random.PRNGKey(rng), cfg, gamma=1, drafter=module_drafter(draft), **kw,
    )


def _long_prompts(B=4, P=12):
    """Prompts longer than the window, rows at unlike depths of padding."""
    rs = np.random.RandomState(1)
    ids = rs.randint(0, 250, (B, P)).astype(np.int32)
    mask = (np.arange(P)[None, :] >= (2 * (np.arange(B) % 4))[:, None]).astype(np.int32)
    ids[mask == 0] = 258
    return jnp.asarray(ids), jnp.asarray(mask)


@pytest.mark.parametrize("eos", [None, 7], ids=["to_the_budget", "eos_stops_rows"])
def test_self_draft_greedy_exactly_matches_plain_sampler(eos):
    """Greedy output of the self-drafting sampler (tokens, mask, logprobs,
    values) is the plain sampler's, through rings shorter than the row: the
    two-token verify at each row's own index reads what the single-token step
    reads."""
    model = _self_drafting_model()
    apply, _, params, tcfg = model
    ids, mask = _long_prompts()
    cfg = GenerationConfig(max_new_tokens=20, do_sample=False, eos_token_id=eos, pad_token_id=258)
    ref = generate(apply, params, lambda b, s: make_kv_cache(tcfg, b, s, jnp.float32),
                   ids, mask, jax.random.PRNGKey(0), cfg)
    out, stats = jax.jit(partial(_self_spec, model, cfg=cfg, return_stats=True))(ids, mask)
    assert (np.asarray(out.response_tokens) == np.asarray(ref.response_tokens)).all()
    assert (np.asarray(out.response_mask) == np.asarray(ref.response_mask)).all()
    np.testing.assert_allclose(out.response_logprobs, ref.response_logprobs, atol=1e-5)
    np.testing.assert_allclose(out.response_values, ref.response_values, atol=1e-5)
    assert int(stats["proposed_draft_tokens"]) == int(stats["live_row_rounds"]) <= 4 * int(stats["rounds"])


def test_identical_draft_accepts_everything_through_rings():
    """With ``q`` equal to ``p`` (the model's own stack as a separate drafter,
    one proposal a round) every proposal is accepted and a round commits two
    tokens: the drafter's single-token writes and the verify's two-token span
    land in the same ring positions, each row at its own index."""
    from trlx_tpu.models.transformer import CausalTransformer

    apply, _, params, tcfg = _self_drafting_model()
    bare = CausalTransformer(tcfg)
    d = (lambda p, i, **k: bare.apply({"params": p}, i, **k), params["backbone"], tcfg)
    ids, mask = _long_prompts()
    cfg = GenerationConfig(max_new_tokens=20, do_sample=True, temperature=1.0, eos_token_id=None, pad_token_id=258)
    out, stats = _spec((apply, params, tcfg), d, ids, mask, cfg, gamma=1, return_stats=True)
    assert np.asarray(out.response_mask).all()
    assert float(stats["acceptance_rate"]) > 0.97 and int(stats["rounds"]) <= 11, stats
    assert abs(float(stats["tokens_per_round"]) - (1 + float(stats["acceptance_rate"]))) < 0.05


def test_self_draft_records_the_targets_logprobs_and_values():
    """Sampled rollouts: the logprob and the value recorded at every position
    are those of ONE scoring forward of the target over the finished rows
    (PPO's ``make_experience`` cannot tell which sampler made them)."""
    model = _self_drafting_model()
    apply, _, params, _ = model
    ids, mask = _long_prompts()
    N = 20
    cfg = GenerationConfig(max_new_tokens=N, do_sample=True, temperature=1.0, eos_token_id=None, pad_token_id=258)
    out, stats = jax.jit(partial(_self_spec, model, cfg=cfg, rng=5, return_stats=True))(ids, mask)
    P = ids.shape[1]
    full = apply(params, out.sequences, attention_mask=jnp.concatenate([mask, out.response_mask], axis=1))
    lp = jax.nn.log_softmax(full["logits"][:, P - 1 : -1].astype(jnp.float32), axis=-1)
    want = jnp.take_along_axis(lp, out.response_tokens[..., None], axis=-1)[..., 0]
    np.testing.assert_allclose(out.response_logprobs, want, atol=2e-5)
    np.testing.assert_allclose(out.response_values, full["value"][:, P - 1 : -1], atol=2e-5)
    assert 0 < int(stats["accepted_draft_tokens"]) < int(stats["proposed_draft_tokens"])  # rows at unlike depths


def test_self_draft_sampling_is_distribution_exact_under_rings_at_unlike_depths():
    """A chi-square of the self-drafting sampler's tokens against the plain
    sampler's, 2048 rows of one prompt each: the marginal of the token at
    response positions 1, 3 and 5 (behind one to three rounds of unlike
    acceptance histories, so rows at unlike depths; the prompt is longer than
    the window, so every ring has wrapped), binned by token id mod 8. Seven
    degrees of freedom: 24.3 is the 0.1% point."""
    model = _self_drafting_model()
    apply, _, params, tcfg = model
    B = 2048
    ids = jnp.tile(jnp.asarray([[5, 9, 17, 23, 40, 41, 77, 3, 200, 150, 99, 12]], jnp.int32), (B, 1))
    mask = jnp.ones_like(ids)
    cfg = GenerationConfig(max_new_tokens=6, do_sample=True, temperature=0.7, eos_token_id=None, pad_token_id=258)
    ref = jax.jit(lambda r: generate(apply, params, lambda b, s: make_kv_cache(tcfg, b, s, jnp.float32),
                                     ids, mask, r, cfg))(jax.random.PRNGKey(3))
    out, stats = jax.jit(partial(_self_spec, model, cfg=cfg, rng=11, return_stats=True))(ids, mask)
    assert 0.02 < float(stats["acceptance_rate"]) < 0.98
    for position in (1, 3, 5):
        a = np.bincount(np.asarray(ref.response_tokens)[:, position] % 8, minlength=8).astype(np.float64)
        b = np.bincount(np.asarray(out.response_tokens)[:, position] % 8, minlength=8).astype(np.float64)
        chi2 = float(((a - b) ** 2 / np.maximum(a + b, 1.0)).sum())
        assert chi2 < 24.3, (position, chi2, a, b)


# ---------------------------------------------------------------------------
# A round's writes at per-row offsets (no loop over the rows)


def _buffers(B, NB, seed=0):
    rs = np.random.RandomState(seed)
    return {
        "tokens": jnp.asarray(rs.randint(0, 250, (B, NB)), jnp.int32),
        "logprobs": jnp.asarray(-rs.rand(B, NB), jnp.float32),
        "values": jnp.asarray(rs.randn(B, NB), jnp.float32),
        "mask": jnp.asarray(rs.randint(0, 2, (B, NB)), jnp.int32),
    }


@pytest.mark.parametrize("name", ["tokens", "logprobs", "values", "mask"])
@pytest.mark.parametrize("G", [1, 4])
def test_block_write_is_the_per_row_dynamic_update_slice(G, name):
    """``write_row_blocks`` (a blend over the buffer) leaves bit for bit what a
    ``dynamic_update_slice`` a row leaves, on each of a round's four buffers:
    a row at offset 0, rows at unlike depths, a row whose offset the clamp
    moved to ``NB - (G + 1)`` and a done row that writes pads over pads. The
    blocks hold what a round writes, invalid entries included (the pad token,
    0.0, 0.0, 0 past a row's committed prefix)."""
    from trlx_tpu.ops.speculative import write_row_blocks

    N, pad = 12, 258
    NB = N + G + 1
    n_out = jnp.asarray([0, 3, N, N - 1, 7, N], jnp.int32)  # rows 2 and 5 ended (row 5 by the budget, earlier)
    B = n_out.shape[0]
    off = jnp.minimum(n_out, NB - (G + 1))  # spec_round_step's clamp
    assert int(off[0]) == 0 and int(off[2]) == NB - (G + 1) and len(set(np.asarray(off).tolist())) >= 4
    rs = np.random.RandomState(G)
    valid = jnp.asarray(np.arange(G + 1)[None, :] < rs.randint(1, G + 2, (B, 1))) & (n_out < N)[:, None]
    block = {
        "tokens": jnp.where(valid, jnp.asarray(rs.randint(0, 250, (B, G + 1)), jnp.int32), pad),
        "logprobs": jnp.where(valid, jnp.asarray(-rs.rand(B, G + 1), jnp.float32), 0.0),
        "values": jnp.where(valid, jnp.asarray(rs.randn(B, G + 1), jnp.float32), 0.0),
        "mask": valid.astype(jnp.int32),
    }[name]
    assert not np.asarray(valid)[2].any() and not np.asarray(valid)[5].any()  # the done rows write pads alone
    buf = _buffers(B, NB)[name]
    want = jnp.stack([jax.lax.dynamic_update_slice(buf[b], block[b], (off[b],)) for b in range(B)])
    got = jax.jit(write_row_blocks)(buf, block, off)
    assert got.dtype == buf.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (np.asarray(got) != np.asarray(buf)).any()


@pytest.mark.parametrize("leaf", ["k", "v"])
@pytest.mark.parametrize("T", [1, 2, 5])
def test_dense_cache_write_is_the_per_row_dynamic_update_slice(T, leaf):
    """``write_row_spans`` (one scatter of ``(row, slot)`` pairs) leaves bit
    for bit, in bf16, what a Python loop of ``dynamic_update_slice`` a row
    leaves in a dense ``[B, S, KV, D]`` cache: rows at unlike indices, one at 0
    and one at the last legal index ``S - T`` (where a round's last probe
    lands), from float32 projections cast on the way in as ``Attention``
    hands them over."""
    from trlx_tpu.models.transformer import write_row_spans

    B, S, KV, D = 5, 19, 2, 8
    rs = np.random.RandomState({"k": 3, "v": 4}[leaf] + T)
    cache = jnp.asarray(rs.randn(B, S, KV, D), jnp.bfloat16)
    x = jnp.asarray(rs.randn(B, T, KV, D), jnp.float32)
    ci = jnp.asarray([0, S - T, 7, 3, 11], jnp.int32)
    want = jnp.stack([
        jax.lax.dynamic_update_slice(cache[b], x[b].astype(jnp.bfloat16), (ci[b], 0, 0)) for b in range(B)
    ])
    got = jax.jit(write_row_spans)(cache, x, ci)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    assert (np.asarray(got[1, S - T :], np.float32) == np.asarray(x[1].astype(jnp.bfloat16), np.float32)).all()


def test_dense_cache_write_drops_a_parked_rows_token():
    """The slot engine's dense segment parks a row that ended by length at
    ``cache_index = S`` and still forwards a pad token for it: that write
    leaves the cache, and is dropped (a clamp would put the pad token's K and V
    over the row's last real slot). The other rows' writes land."""
    from trlx_tpu.models.transformer import write_row_spans

    B, S, KV, D = 3, 9, 2, 4
    cache = jnp.zeros((B, S, KV, D), jnp.bfloat16)
    got = write_row_spans(cache, jnp.ones((B, 1, KV, D), jnp.float32), jnp.asarray([2, S, S - 1], jnp.int32))
    assert float(jnp.sum(got[1].astype(jnp.float32))) == 0.0
    assert float(jnp.sum(got[0].astype(jnp.float32))) == float(jnp.sum(got[2].astype(jnp.float32))) == KV * D
    assert (np.asarray(got[2, S - 1], np.float32) == 1.0).all()


def _scatters(jaxpr):
    """Every ``scatter*`` equation of a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scatters(sub)


def _row_serial(eqn, buffers=()):
    """Whether the chip would run this scatter one row at a time: batched over
    the rows (``operand_batching_dims``), or over an output buffer at all."""
    return bool(eqn.params["dimension_numbers"].operand_batching_dims) or eqn.invars[0].aval.shape in buffers


def test_a_round_has_no_scatter_batched_over_the_rows():
    """No write of a speculative round may come back as a scatter with
    ``operand_batching_dims``, which is what ``jax.vmap`` makes of a per-row
    ``dynamic_update_slice``, and none may take a ``[B, NB]`` output buffer as
    its operand: the chip's compiler runs that form one row at a time, a
    ``while`` of 64 dynamic-update-slices a write a round, which was 1.40 s of
    cell 9's 17.5 s cycle in eight such loops (``PERF.md`` section 5, cell 9;
    section 6, PR 47). The round's body is walked with the model's own
    sub-jaxprs: the stack's verify, the module's draft, four rings and two
    dense caches under a ``[B]`` cache index."""
    model = _self_drafting_model()
    ids, mask = _long_prompts()
    N, G = 20, 1
    cfg = GenerationConfig(max_new_tokens=N, do_sample=True, temperature=1.0, eos_token_id=7, pad_token_id=258)
    whole = jax.make_jaxpr(partial(_self_spec, model, cfg=cfg))(ids, mask).jaxpr
    rounds = [e for e in whole.eqns if e.primitive.name == "while"]
    assert len(rounds) == 1
    body = rounds[0].params["body_jaxpr"].jaxpr
    found = list(_scatters(body))
    # K and V of four rings and two dense caches (the stack's layers and the module's): twelve
    # scatters over a cache, beside the sparse layers' own over their routing tables
    shapes = [e.invars[0].aval.shape for e in found]
    assert sum(len(s) == 4 for s in shapes) == 2 * (model[3].num_layers + 1), shapes
    B, NB = ids.shape[0], N + G + 1
    bad = [e for e in found if _row_serial(e, buffers={(B, NB)})]
    assert not bad, [(str(e.invars[0].aval), e.params["dimension_numbers"]) for e in bad]
    # and the walk does see the form it guards against
    old = jax.make_jaxpr(jax.vmap(lambda b, x, o: jax.lax.dynamic_update_slice(b, x, (o,))))(
        jnp.zeros((B, NB)), jnp.ones((B, G + 1)), jnp.zeros((B,), jnp.int32)
    ).jaxpr
    assert [_row_serial(e) for e in _scatters(old)] == [True]
