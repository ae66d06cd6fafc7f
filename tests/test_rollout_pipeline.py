"""Software-pipelined experience collection (docs/PERFORMANCE.md).

Three contracts, per the pipeline's design:

- **equivalence** — depth ≥ 1 produces a bit-identical rollout store and
  identical ``exp_scores/*`` statistics vs the depth-0 serial path under a
  fixed seed (the overlap is exact, not approximate: params don't change
  within one ``make_experience``);
- **failure** — a ``reward_fn`` that raises on the worker propagates out of
  ``make_experience``, with the pipeline drained and no leaked thread;
- **overlap** — with an artificially slow reward fn, host work hides behind
  device generation: ``throughput/rollout_overlap_frac`` > 0 and the
  pipelined wall-time beats serial on the same seed.

Plus unit tests of the :class:`RolloutPipeline` state machine itself.
"""

import dataclasses
import threading
import time

import numpy as np
import pytest

from trlx_tpu.pipeline.rollout_pipeline import RolloutPipeline

_WORKER_NAME = "trlx-rollout-pipeline"


def _pipeline_threads():
    return [t for t in threading.enumerate() if t.name == _WORKER_NAME and t.is_alive()]


# ---------------------------------------------------------------------------
# RolloutPipeline unit tests (no trainer, no jax)
# ---------------------------------------------------------------------------


class TestRolloutPipeline:
    def test_ordered_finalize_under_varying_work_times(self):
        done = []
        with RolloutPipeline(depth=3, finalize=done.append) as pipe:
            for i in range(8):
                # earlier chunks sleep longer: order must still hold
                pipe.submit(lambda i=i: (time.sleep(0.02 * (8 - i)), i)[1])
        assert done == list(range(8))
        assert pipe.stats.chunks == 8
        assert pipe.stats.host_work_s > 0

    def test_backpressure_bounds_in_flight(self):
        active = []
        peak = []
        lock = threading.Lock()

        def work(i):
            with lock:
                active.append(i)
                peak.append(len(active))
            time.sleep(0.01)
            with lock:
                active.remove(i)
            return i

        done = []
        with RolloutPipeline(depth=2, finalize=done.append) as pipe:
            submitted_while_full = []
            for i in range(6):
                submitted_while_full.append(pipe.in_flight)
                pipe.submit(lambda i=i: work(i))
        # one worker: never more than 1 running; in-flight (queued +
        # running + unfinalized) never exceeds depth at submit time
        assert max(peak) == 1
        assert max(submitted_while_full) <= 2
        assert done == list(range(6))

    def test_worker_exception_propagates_and_joins(self):
        class Boom(RuntimeError):
            pass

        def bad():
            raise Boom("reward exploded")

        done = []
        pipe = RolloutPipeline(depth=2, finalize=done.append)
        pipe.submit(lambda: 1)
        pipe.submit(bad)
        with pytest.raises(Boom, match="reward exploded"):
            # the failure surfaces on the next interaction; keep submitting
            # until it does (backpressure may need a round trip)
            for _ in range(10):
                pipe.submit(lambda: 2)
                time.sleep(0.01)
            pipe.drain()
        assert _pipeline_threads() == []  # worker joined on failure
        # the completed prefix finalized deterministically before the failure
        assert done[0] == 1

    def test_finalize_exception_cancels(self):
        def finalize(r):
            raise ValueError("finalize rejects")

        with pytest.raises(ValueError, match="finalize rejects"):
            with RolloutPipeline(depth=1, finalize=finalize) as pipe:
                pipe.submit(lambda: 1)
                pipe.submit(lambda: 2)  # forces retirement of chunk 1
                pipe.drain()
        assert _pipeline_threads() == []

    def test_depth_validation(self):
        with pytest.raises(ValueError):
            RolloutPipeline(depth=0)

    def test_overlap_accounting(self):
        # worker busy 4×30ms while the submitter "computes" 4×30ms: most
        # host work should be hidden, a drain tail may expose some
        with RolloutPipeline(depth=2, finalize=lambda r: r) as pipe:
            t0 = time.perf_counter()
            for _ in range(4):
                pipe.submit(lambda: time.sleep(0.03))
                time.sleep(0.03)  # stand-in for device work
            pipe.drain()
            total = time.perf_counter() - t0
        frac = pipe.stats.overlap_frac(total)
        assert 0.0 < frac <= 1.0
        assert pipe.stats.overlap_s > 0.03  # more than one chunk hidden


# ---------------------------------------------------------------------------
# PPO and GRPO make_experience: pipelined vs serial
# ---------------------------------------------------------------------------

PROMPTS = ["hello world", "the quick brown fox", "lorem ipsum", "foo bar"] * 4


# uneven prompts: sorted into four groups of two their longest rows are 5, 12, 21 and 38 tokens
MIXED_PROMPTS = ["abc", "hello", "lorem ip", "hello world!", "the quick brown", "the quick brown fox j",
                 "pack my box with five dozen", "sphinx of black quartz, judge my vow!!"] * 2


def _trainer(method, tmp_path, depth, reward_fn, tag, prompts=PROMPTS, batch_size=8, chunk_size=4):
    import trlx_tpu.pipeline.offline_pipeline  # noqa: F401 (registration)
    import trlx_tpu.trainer.grpo  # noqa: F401 (registration)
    import trlx_tpu.trainer.ppo  # noqa: F401 (registration)
    from trlx_tpu.data.default_configs import default_grpo_config, default_ppo_config
    from trlx_tpu.pipeline import get_pipeline
    from trlx_tpu.trainer import get_trainer

    default = default_grpo_config if method == "grpo" else default_ppo_config
    extra = dict(group_size=2) if method == "grpo" else {}
    cfg = default().evolve(
        train=dict(
            seq_length=48,
            batch_size=batch_size,
            total_steps=4,
            checkpoint_interval=1000,
            checkpoint_dir=str(tmp_path / f"ckpts_{tag}"),
            tracker=None,
            rollout_pipeline_depth=depth,
        ),
        model=dict(model_path="builtin:gpt2-test", num_layers_unfrozen=1),
        tokenizer=dict(tokenizer_path="builtin:bytes"),
        method=dict(
            num_rollouts=16,
            chunk_size=chunk_size,
            ppo_epochs=1,
            gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0, do_sample=True),
            **extra,
        ),
    )
    trainer = get_trainer(cfg.train.trainer)(
        config=cfg, reward_fn=reward_fn, metric_fn=None, stop_sequences=[]
    )
    trainer.add_prompt_pipeline(
        get_pipeline(cfg.train.pipeline)(prompts, 40, trainer.tokenizer)
    )
    return trainer


def _slow_letter_reward(samples, prompts, outputs, **kwargs):
    # an artificially expensive host-side reward. Deliberately large: the
    # sleep is pure hideable time (releases the GIL, needs no core), so the
    # pipelined-vs-serial margin (~3 hidden sleeps ≈ 450ms) dwarfs 1-core
    # CI noise; thinner sleeps flaked when generation contends for the core
    time.sleep(0.15)
    return [float(sum(c in "aeiou" for c in o)) for o in outputs]


def _assert_stores_identical(store_a, store_b):
    assert len(store_a) == len(store_b)
    for a, b in zip(store_a.history, store_b.history):
        # PPORLElement or GRPORLElement: every field, behavior_logprobs (None
        # off the async path) included
        for field in dataclasses.fields(a):
            np.testing.assert_array_equal(
                np.asarray(getattr(a, field.name)), np.asarray(getattr(b, field.name)),
                err_msg=field.name,
            )


class TestPipelinedExperience:
    @pytest.mark.parametrize("method", ["ppo", "grpo"])
    def test_bit_identical_to_serial_and_overlapped(self, tmp_path, method):
        """Acceptance: depth 2 + a 60ms/chunk reward → same store, same
        exp_scores/*, overlap_frac > 0 on one worker thread. (No comparison
        of wall-clock seconds: on a shared CPU either side may be the slower.)"""
        serial = _trainer(method, tmp_path, 0, _slow_letter_reward, "serial")
        piped = _trainer(method, tmp_path, 2, _slow_letter_reward, "piped")

        # first call covers compile; stores must already match bit-for-bit
        serial.make_experience(16)
        piped.make_experience(16)
        _assert_stores_identical(serial.store, piped.store)

        # warm pass: same seed trajectory on both (running moments and
        # rollout RNG advanced identically above)
        serial.store.clear_history()
        piped.store.clear_history()
        serial.make_experience(16)
        piped.make_experience(16)

        _assert_stores_identical(serial.store, piped.store)
        keys = ["exp_scores/mean", "exp_scores/std"]
        if method == "ppo":  # GRPO keeps the running moments but publishes none
            keys += ["exp_scores/running_mean", "exp_scores/running_std"]
        for key in keys:
            assert (
                serial.make_experience_stats[key] == piped.make_experience_stats[key]
            ), key
        assert serial.running_moments.mean == piped.running_moments.mean
        assert serial.running_moments.std == piped.running_moments.std

        assert serial.make_experience_stats["throughput/rollout_overlap_frac"] == 0.0
        assert piped.make_experience_stats["throughput/rollout_overlap_frac"] > 0.0
        assert piped.make_experience_stats["time/rollout_host"] > 0.0
        assert _pipeline_threads() == []

        # both make_experience calls spawned their own worker thread, but
        # the trace shows ONE named track (stable aliased tid), not one
        # near-empty row per collection cycle
        events = piped.obs.tracer.events()
        overlap_tids = {e["tid"] for e in events if e["name"] == "rollout/overlap"}
        assert len(overlap_tids) == 1, overlap_tids
        names = [
            e for e in events
            if e.get("ph") == "M" and e["args"]["name"] == "rollout pipeline worker"
        ]
        assert len(names) == 1 and names[0]["tid"] in overlap_tids

    @pytest.mark.parametrize("method", ["ppo", "grpo"])
    def test_length_grouped_scoring_is_bit_identical_to_serial(self, tmp_path, monkeypatch, method):
        """Chunks of uneven prompts are scored in length groups, one dispatch
        a group (trainer/ppo.py::score_groups): the worker lands them all and
        the store is the serial path's to the bit on that path too."""
        from trlx_tpu.pipeline import ppo_pipeline

        monkeypatch.setattr(ppo_pipeline, "LADDER_BASE", 8)  # a query budget of 40: scoring's rungs 8 and 40
        kw = dict(prompts=MIXED_PROMPTS, batch_size=2, chunk_size=8)
        serial = _trainer(method, tmp_path, 0, _slow_letter_reward, "serial", **kw)
        piped = _trainer(method, tmp_path, 2, _slow_letter_reward, "piped", **kw)
        for _ in range(2):  # the second pass is warm
            serial.store.clear_history()
            piped.store.clear_history()
            serial.make_experience(16)
            piped.make_experience(16)
            _assert_stores_identical(serial.store, piped.store)
        for trainer in (serial, piped):
            shapes = sorted(trainer._score_fns)
            # groups of two rows at rung 8 and at their chunk's width (16 to 40: a chunk narrower
            # than the top rung keeps its own width there); a chunk with no prompt under 9 tokens whole
            assert {b for b, _, _ in shapes} <= {2, 8} and (2, 8, 8) in shapes, shapes
            assert {p for _, p, _ in shapes} <= {8, 16, 24, 32, 40}
            assert trainer.make_experience_stats["collect/score_shapes"] == float(len(shapes))
            assert 0.0 < trainer.make_experience_stats["collect/score_pad_frac"] < 0.5
        assert piped.make_experience_stats["throughput/rollout_overlap_frac"] > 0.0
        assert _pipeline_threads() == []

    def test_reward_error_propagates_no_leaked_worker(self, tmp_path):
        calls = {"n": 0}

        def exploding_reward(samples, prompts, outputs, **kwargs):
            calls["n"] += 1
            if calls["n"] >= 2:
                raise RuntimeError("reward backend down")
            return [0.0] * len(outputs)

        trainer = _trainer("ppo", tmp_path, 2, exploding_reward, "err")
        with pytest.raises(RuntimeError, match="reward backend down"):
            trainer.make_experience(16)
        assert _pipeline_threads() == []  # drained and joined, not leaked

    @pytest.mark.parametrize("method", ["ppo", "grpo"])
    def test_depth_zero_is_the_reference_path(self, tmp_path, method):
        """The serial path never constructs a pipeline (no worker thread)."""
        trainer = _trainer(method, tmp_path, 0, _slow_letter_reward, "ref")
        trainer.make_experience(8)
        assert len(trainer.store) == 8
        assert _pipeline_threads() == []

    @pytest.mark.parametrize("method", ["ppo", "grpo"])
    def test_nonfinite_scores_are_zeroed_before_the_moments(self, tmp_path, method):
        """One NaN and one inf from a reward endpoint are zeroed, counted,
        and never reach the cumulative running moments."""

        def flaky_reward(samples, prompts, outputs, **kwargs):
            scores = [float(len(o)) for o in outputs]
            scores[0], scores[-1] = float("nan"), float("inf")
            return scores

        trainer = _trainer(method, tmp_path, 0, flaky_reward, "nan")
        trainer.make_experience(8)
        assert len(trainer.store) == 8
        assert trainer.make_experience_stats["health/nonfinite_scores"] == 4.0
        moments = trainer.running_moments
        assert np.isfinite([moments.mean, moments.std, moments.var]).all()
        fields = ("advantage",) if method == "grpo" else ("rewards",)
        for element in trainer.store.history:
            assert all(np.isfinite(getattr(element, f)).all() for f in fields)


# ---------------------------------------------------------------------------
# ILQL offline make_experience: pipelined tokenization
# ---------------------------------------------------------------------------


def test_ilql_pipelined_tokenization_identical():
    from trlx_tpu.data.configs import TokenizerConfig
    from trlx_tpu.data.tokenizer import from_config
    from trlx_tpu.trainer.ilql import make_experience, make_experience_seq2seq

    tokenizer = from_config(TokenizerConfig(tokenizer_path="builtin:bytes"))
    # 150 samples > the 64-sample tokenization chunk, so the pipelined path
    # actually engages (several chunks in flight)
    samples = [[f"prompt {i}: ", f"output {i % 7}"] for i in range(150)]
    rewards = [float(i % 5) for i in range(150)]

    for fn in (make_experience, make_experience_seq2seq):
        serial = fn(samples, rewards, tokenizer, max_length=64, verbose=False)
        piped = fn(
            samples, rewards, tokenizer, max_length=64, verbose=False,
            pipeline_depth=2,
        )
        assert len(serial.history) == len(piped.history) == 150
        for a, b in zip(serial.history, piped.history):
            for sv, pv in zip(
                a.__dict__.values() if hasattr(a, "__dict__") else a,
                b.__dict__.values() if hasattr(b, "__dict__") else b,
            ):
                np.testing.assert_array_equal(np.asarray(sv), np.asarray(pv))
    assert [t for t in threading.enumerate() if t.name == "trlx-ilql_tokenize-pipeline"] == []
