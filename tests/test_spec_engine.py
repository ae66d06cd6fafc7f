"""Speculative continuous batching (docs/PERFORMANCE.md "Speculative
continuous batching"): draft-model decode segments for the paged Engine.

The pinned contract: with ``engine.speculative = k`` on, every sequence
harvested from the continuous-batching Engine is the solo
``ops/speculative.py`` run of that row under its per-row RNG chain — tokens
and mask bit for bit, logprobs and values to a few float32 ulp (see
``FLOAT_ULPS``) — regardless of block size, prefix hits, refills, chunked
prefill, or segment size. The mechanism is structural:
the segment's round body IS ``ops/speculative.py::spec_round_step`` (one
function, not mirrored code), so these tests pin the paged plumbing around
it — the gather/scatter commit discipline, the refill prefills, and the
engine's variable-advance step accounting.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.data.configs import ModelConfig
from trlx_tpu.engine.core import ContinuousEngine
from trlx_tpu.models.builder import build_causal_lm
from trlx_tpu.models.transformer import make_kv_cache
from trlx_tpu.ops.paged_kv import PagedSpec, num_table_blocks
from trlx_tpu.ops.sampling import GenerationConfig, per_row_keys
from trlx_tpu.ops.slot_refill import make_slot_refill_fns
from trlx_tpu.ops.speculative import generate_speculative

B, P, N, G = 2, 8, 10, 3
FIELDS = ("tokens", "logprobs", "values", "mask")


@pytest.fixture(scope="module")
def models():
    kw = dict(model_extra_kwargs=dict(dtype=jnp.float32, param_dtype=jnp.float32))
    t_mod, t_params, t_cfg = build_causal_lm(
        ModelConfig("builtin:gpt2-test", **kw), head="value"
    )
    d_mod, d_params, d_cfg = build_causal_lm(
        ModelConfig("builtin:gpt2-test", **kw), head=None, seed=1
    )
    return {
        "t_apply": lambda p, i, **k: t_mod.apply({"params": p}, i, **k),
        "d_apply": lambda p, i, **k: d_mod.apply({"params": p}, i, **k),
        "t_init": lambda b, s: make_kv_cache(t_cfg, b, s, jnp.float32),
        "d_init": lambda b, s: make_kv_cache(d_cfg, b, s, jnp.float32),
        "t_params": t_params,
        "d_params": d_params,
    }


def _prompts(R=5):
    """R requests through B=2 slots — forces mid-collection refill waves;
    row 4 repeats row 1's prompt so the prefix cache gets a hit."""
    rs = np.random.RandomState(0)
    ids = rs.randint(0, 250, (R, P)).astype(np.int32)
    mask = np.ones((R, P), np.int32)
    mask[0, :3] = 0
    if R > 2:
        mask[2, :5] = 0
    ids[mask == 0] = 258
    if R > 4:
        ids[4] = ids[1]
        mask[4] = mask[1]
    keys = np.asarray(per_row_keys(jax.random.PRNGKey(0), R))
    return ids, mask, keys


def _gen_config(**kw):
    base = dict(
        max_new_tokens=N, do_sample=True, temperature=0.7,
        eos_token_id=257, pad_token_id=258, per_row_rng=True,
    )
    base.update(kw)
    return GenerationConfig(**base)


def _solo_rows(m, ids, mask, keys, cfg, transition_mask=None):
    """Solo generate_speculative per row — the bit-parity references."""
    refs = []
    for i in range(ids.shape[0]):
        out = generate_speculative(
            m["t_apply"], m["t_params"], m["d_apply"], m["d_params"],
            m["t_init"], m["d_init"],
            jnp.asarray(ids[i:i + 1]), jnp.asarray(mask[i:i + 1]),
            jnp.asarray(keys[i:i + 1]), cfg, gamma=G,
            transition_mask=transition_mask,
        )
        refs.append({
            "tokens": np.asarray(out.response_tokens)[0],
            "logprobs": np.asarray(out.response_logprobs)[0],
            "values": np.asarray(out.response_values)[0],
            "mask": np.asarray(out.response_mask)[0],
        })
    return refs


@pytest.fixture(scope="module")
def solo_refs(models):
    ids, mask, keys = _prompts()
    return _solo_rows(models, ids, mask, keys, _gen_config())


def _spec_fns(m, block_size, segment_len, transition_mask=None, **kw):
    S = P + N + G
    TB = num_table_blocks(S, block_size)
    paged = PagedSpec(block_size=block_size, max_blocks=1 + 3 * B * TB)
    return make_slot_refill_fns(
        m["t_apply"], m["t_init"], B, P,
        kw.pop("config", _gen_config()),
        segment_len=segment_len,
        paged=paged,
        speculative=G,
        draft_apply=kw.pop("draft_apply", m["d_apply"]),
        init_draft_cache_fn=kw.pop("init_draft_cache_fn", m["d_init"]),
        transition_mask=transition_mask,
        **kw,
    )


def _harvest_all(m, fns, ids, mask, keys, params=None, prefill_chunk=0):
    eng = ContinuousEngine(
        fns,
        (m["t_params"], m["d_params"]) if params is None else params,
        258, prefix_cache=True, prefill_chunk=prefill_chunk,
    )
    eng.begin_collection(eng.params)
    eng.enqueue_prompts(ids, mask, keys)
    got = {}
    while eng.busy:
        for c in eng.step():
            got[c.index] = {
                "tokens": c.tokens, "logprobs": c.logprobs,
                "values": c.values, "mask": c.mask,
            }
    return got, eng


# The engine and the solo reference share the round body but not the program
# around it: the engine's forward runs B=2 rows over a paged (gathered) cache,
# the solo run one row over a dense one, and XLA tiles and orders the two
# matmul reductions differently. The float outputs therefore agree to the last
# bit or two of float32 (seen: 1 ulp on a logprob near 6, 2e-8 on a value near
# 0.01), not bitwise. What sampling decides stays exact: tokens and masks.
FLOAT_ULPS = 4 * float(np.finfo(np.float32).eps)


def _assert_parity(got, refs, ctx):
    assert sorted(got) == list(range(len(refs)))
    for i, ref in enumerate(refs):
        for f in FIELDS:
            err_msg = f"{ctx}: request {i} field {f}"
            if f in ("tokens", "mask"):
                np.testing.assert_array_equal(np.asarray(got[i][f]), ref[f], err_msg=err_msg)
            else:
                np.testing.assert_allclose(
                    np.asarray(got[i][f]), ref[f], rtol=FLOAT_ULPS, atol=FLOAT_ULPS,
                    err_msg=err_msg,
                )


class TestBitParity:
    def test_refills_and_prefix_hits(self, models, solo_refs):
        """5 requests through 2 slots at block size 4: mid-collection
        refill waves, one prefix-cache hit, and every harvested row
        bit-equal to its solo run."""
        ids, mask, keys = _prompts()
        fns = _spec_fns(models, block_size=4, segment_len=2)
        got, eng = _harvest_all(models, fns, ids, mask, keys)
        _assert_parity(got, solo_refs, "bs=4")
        m = eng.stats.metrics()
        assert m["engine/prefix_hit_rate"] > 0.0  # the repeated prompt hit
        assert m["rollout/spec_rounds"] > 0
        assert 0.0 < m["engine/spec_acceptance_rate"] <= 1.0
        assert 1.0 <= m["engine/spec_tokens_per_round"] <= G + 1
        assert m["engine/spec_verify_kernel_pallas"] == 0.0  # xla verify
        # spec segments commit multiple tokens per round: total committed
        # tokens exceed the rounds run (the whole point of the program)
        assert eng.stats.spec_committed > eng.stats.spec_rounds

    def test_pallas_kernels_compose(self, models, solo_refs):
        """ISSUE 18 acceptance: engine.speculative no longer forces the
        gather-reference kernels. With decode_kernel AND prefill_kernel
        pallas the spec segment runs in place — the width-``G + 1`` verify
        forwards read K/V through the multi-position verify kernel
        (``ops/paged_attention.py::paged_verify_attention``) and commit
        probe columns through per-row done-poisoned block tables — and
        every harvested row stays bit-identical to its solo run (and hence
        to the xla-kernel spec path, which pins against the same refs)."""
        ids, mask, keys = _prompts()
        fns = _spec_fns(
            models, block_size=4, segment_len=2,
            decode_kernel="pallas", prefill_kernel="pallas",
        )
        got, eng = _harvest_all(models, fns, ids, mask, keys)
        _assert_parity(got, solo_refs, "pallas kernels")
        assert eng.stats.spec_rounds > 0
        # the verify-compute stamp must survive the per-collection stats
        # reset (begin_collection rebuilds EngineStats; regression — the
        # stamp used to be dropped there and always read 0)
        m = eng.stats.metrics()
        assert m["engine/spec_verify_kernel_pallas"] == 1.0

    def test_odd_blocks_and_chunked_prefill(self, models, solo_refs):
        """Block size 3 (nothing aligns: P=8, S=21) with chunked prefill —
        prompts admit in 4-column spans between decode segments — stays
        bit-identical: the chunk programs only commit TARGET prompt K/V,
        the draft prefills whole at seed time."""
        ids, mask, keys = _prompts()
        fns = _spec_fns(models, block_size=3, segment_len=2)
        got, _ = _harvest_all(models, fns, ids, mask, keys, prefill_chunk=4)
        _assert_parity(got, solo_refs, "bs=3 chunk=4")

    @pytest.mark.slow
    @pytest.mark.parametrize("block_size", [1, 8])
    def test_block_size_extremes(self, models, solo_refs, block_size):
        ids, mask, keys = _prompts()
        fns = _spec_fns(models, block_size=block_size, segment_len=2)
        got, _ = _harvest_all(models, fns, ids, mask, keys)
        _assert_parity(got, solo_refs, f"bs={block_size}")

    @pytest.mark.slow
    @pytest.mark.parametrize("segment_len", [1, 4])
    def test_segment_size_invariance(self, models, solo_refs, segment_len):
        """Rounds-per-segment is a scheduling knob: harvests are identical
        whether the host syncs after every round or every 4."""
        ids, mask, keys = _prompts()
        fns = _spec_fns(models, block_size=4, segment_len=segment_len)
        got, _ = _harvest_all(models, fns, ids, mask, keys)
        _assert_parity(got, solo_refs, f"seg={segment_len}")

    @pytest.mark.slow
    def test_transition_mask_parity(self, models):
        """The trainer's transition logit mask rides the spec segment the
        serial way — applied to draft AND target inside the shared round —
        and an absorbing mask makes lengths heterogeneous, so rows really
        do finish (and refill) at different rounds."""
        V, eos = 259, 257
        tmask = np.ones((V, V), bool)
        tmask[0:64, :] = False
        tmask[0:64, eos] = True
        tmask = jnp.asarray(tmask)
        ids, mask, keys = _prompts()
        refs = _solo_rows(models, ids, mask, keys, _gen_config(),
                          transition_mask=tmask)
        fns = _spec_fns(models, block_size=4, segment_len=2,
                        transition_mask=tmask)
        got, _ = _harvest_all(models, fns, ids, mask, keys)
        _assert_parity(got, refs, "transition-mask")
        lens = {int(np.asarray(r["mask"]).sum()) for r in refs}
        assert len(lens) > 1  # absorbing mask → heterogeneous finishes


class TestAcceptanceAccounting:
    """Forced-outcome drafts pin the acceptance counters exactly: a draft
    that IS the target accepts everything (acceptance 1.0, gamma+1 tokens
    per round); a draft whose proposals the target forbids rejects
    everything (acceptance 0.0 — each round commits exactly the residual
    token, 1/(gamma+1) of the per-round maximum)."""

    def test_accept_all_and_reject_all(self, models):
        ids, mask, keys = _prompts(R=2)
        # N a multiple of (G+1): no partial final round to blur the exact
        # per-round accounting
        cfg = _gen_config(max_new_tokens=G + 1, do_sample=False,
                          eos_token_id=None)

        # accept-all: the draft IS the target (same apply, same params)
        fns = _spec_fns(
            models, block_size=4, segment_len=2, config=cfg,
            draft_apply=models["t_apply"], init_draft_cache_fn=models["t_init"],
        )
        _, eng = _harvest_all(models, fns, ids, mask, keys,
                              params=(models["t_params"], models["t_params"]))
        assert eng.stats.spec_acceptance_rate == 1.0
        assert eng.stats.spec_tokens_per_round == G + 1

        # reject-all: draft always proposes token 3; the target's adjust
        # hook forbids it (greedy verify: argmax != 3 → reject), so every
        # round commits exactly the one residual token
        def draft_force_3(p, ids_, **kw):
            out = models["d_apply"](p, ids_, **kw)
            logits = jnp.full_like(out["logits"], -1e9).at[..., 3].set(0.0)
            return {**out, "logits": logits}

        fns = _spec_fns(
            models, block_size=4, segment_len=2, config=cfg,
            draft_apply=draft_force_3,
            adjust_logits=lambda step_out, logits: logits.at[..., 3].set(-1e9),
        )
        _, eng = _harvest_all(models, fns, ids, mask, keys)
        assert eng.stats.spec_acceptance_rate == 0.0
        assert eng.stats.spec_tokens_per_round == 1.0
        assert eng.stats.spec_tokens_per_round / (G + 1) == 1.0 / (G + 1)


class TestValidation:
    """Each composition precondition is its own precise error."""

    def test_requires_paged(self, models):
        with pytest.raises(ValueError, match="paged KV backend"):
            make_slot_refill_fns(
                models["t_apply"], models["t_init"], B, P, _gen_config(),
                speculative=G, draft_apply=models["d_apply"],
                init_draft_cache_fn=models["d_init"],
            )

    def test_requires_draft(self, models):
        paged = PagedSpec(block_size=4, max_blocks=64)
        with pytest.raises(ValueError, match="draft model"):
            make_slot_refill_fns(
                models["t_apply"], models["t_init"], B, P, _gen_config(),
                paged=paged, speculative=G,
            )

    def test_requires_per_row_rng(self, models):
        paged = PagedSpec(block_size=4, max_blocks=64)
        with pytest.raises(ValueError, match="per-row RNG"):
            make_slot_refill_fns(
                models["t_apply"], models["t_init"], B, P,
                _gen_config(per_row_rng=False),
                paged=paged, speculative=G, draft_apply=models["d_apply"],
                init_draft_cache_fn=models["d_init"],
            )

    def test_trainer_config_validation(self, tmp_path):
        """The trainer rejects each misconfiguration at construction, not
        at the first rollout collection."""
        import trlx_tpu.trainer.ppo  # noqa: F401 (registration)
        from trlx_tpu.data.default_configs import default_ppo_config
        from trlx_tpu.trainer import get_trainer

        def build(**over):
            cfg = default_ppo_config().evolve(
                train=dict(
                    tracker=None, checkpoint_dir=str(tmp_path / "ck"),
                    continuous_batching=True,
                ),
                **over,
            )
            return get_trainer(cfg.train.trainer)(
                config=cfg, reward_fn=lambda *a, **k: [0.0],
                metric_fn=None, stop_sequences=[],
            )

        with pytest.raises(ValueError, match="draft_model_path"):
            build(engine=dict(backend="paged", speculative=2))
        with pytest.raises(ValueError, match="backend: paged"):
            build(
                engine=dict(speculative=2),
                model=dict(
                    model_path="builtin:gpt2-test",
                    draft_model_path="builtin:gpt2-test",
                ),
            )
        with pytest.raises(ValueError, match="must be >= 0"):
            build(engine=dict(backend="paged", speculative=-1))
        # spec + pallas kernels now COMPOSE (the verify kernel): the old
        # decode_kernel blocker is gone — construction succeeds
        t = build(
            engine=dict(
                backend="paged", speculative=2, decode_kernel="pallas",
                kv_block_size=4,
            ),
            model=dict(
                model_path="builtin:gpt2-test",
                draft_model_path="builtin:gpt2-test",
            ),
        )
        assert t is not None


@pytest.mark.slow
class TestPPOEndToEnd:
    def test_spec_cb_store_matches_serial_spec(self, tmp_path):
        """Acceptance: a PPO collection through the speculative
        continuous-batching Engine fills the SAME store (logprobs, values,
        rewards bit-equal per sequence) as the serial speculative sampler
        with per-row RNG — order aside, speculation under continuous
        batching is invisible to training."""
        import trlx_tpu.pipeline.offline_pipeline  # noqa: F401
        import trlx_tpu.trainer.ppo  # noqa: F401
        from trlx_tpu.data.default_configs import default_ppo_config
        from trlx_tpu.pipeline import get_pipeline
        from trlx_tpu.trainer import get_trainer

        prompts = ["hello world", "the quick brown fox", "lorem ipsum",
                   "foo bar"] * 4
        V, eos = 259, 257
        tmask = np.ones((V, V), bool)
        tmask[0:64, :] = False
        tmask[0:64, eos] = True

        def reward(samples, prompts, outputs, **kwargs):
            return [float(sum(c in "aeiou" for c in o)) for o in outputs]

        def trainer_for(tag, continuous):
            cfg = default_ppo_config().evolve(
                train=dict(
                    seq_length=48, batch_size=8, total_steps=4,
                    checkpoint_interval=1000,
                    checkpoint_dir=str(tmp_path / f"ckpts_{tag}"),
                    tracker=None, rollout_pipeline_depth=0,
                    continuous_batching=continuous,
                    continuous_batching_segment=3,
                ),
                model=dict(
                    model_path="builtin:gpt2-test", num_layers_unfrozen=1,
                    draft_model_path="builtin:gpt2-test", draft_gamma=G,
                ),
                engine=(
                    dict(backend="paged", kv_block_size=4, speculative=G)
                    if continuous else dict()
                ),
                method=dict(
                    num_rollouts=16, chunk_size=4, ppo_epochs=1,
                    gen_kwargs=dict(
                        max_new_tokens=8, top_k=0, top_p=1.0,
                        do_sample=True, per_row_rng=True,
                    ),
                ),
            )
            t = get_trainer(cfg.train.trainer)(
                config=cfg, reward_fn=reward, metric_fn=None,
                stop_sequences=[], logit_mask=tmask,
            )
            t.add_prompt_pipeline(
                get_pipeline(cfg.train.pipeline)(prompts, 40, t.tokenizer)
            )
            return t

        serial = trainer_for("serial", continuous=False)
        spec_cb = trainer_for("spec_cb", continuous=True)
        serial.make_experience(16)
        spec_cb.make_experience(16)

        assert len(serial.store) == len(spec_cb.store) == 16

        def canonical(store):
            return {
                (
                    tuple(np.asarray(e.query_tensor).tolist()),
                    tuple(np.asarray(e.response_tensor).tolist()),
                ): e
                for e in store.history
            }

        a, b = canonical(serial.store), canonical(spec_cb.store)
        assert set(a) == set(b)
        for key in a:
            for field in ("logprobs", "values", "rewards"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(a[key], field)),
                    np.asarray(getattr(b[key], field)),
                    err_msg=field,
                )
        stats = spec_cb.make_experience_stats
        assert stats["engine/spec_acceptance_rate"] > 0.0
        assert stats["rollout/spec_rounds"] > 0
        assert 1.0 <= stats["engine/spec_tokens_per_round"] <= G + 1
