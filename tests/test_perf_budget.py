"""Hardware-free perf regression net (round-3 verdict #2).

Recompiles the three hot programs (rollout generate, scoring forward, train
step) with abstract weights and asserts XLA's compiled cost model against the
committed budgets in ``benchmarks/perf_budgets.json``. Catches program-level
perf regressions — an extra forward, a lost logits-span restriction, broken
remat, a fusion-killing graph change — while no accelerator is available.
Budgets regenerate via ``scripts/update_perf_budgets.py`` after intentional
hot-path changes. See ``trlx_tpu/perf.py``.
"""

import json
import os

import pytest

from trlx_tpu.perf import budget_configs, check_budget, hot_program_costs

BUDGET_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks",
    "perf_budgets.json",
)


def _budget(name):
    with open(BUDGET_PATH) as f:
        payload = json.load(f)
    entry = dict(payload["budgets"][name])
    shape = entry.pop("shape")
    return entry, shape


def _assert_within_budget(name):
    budget, shape = _budget(name)
    config, _ = budget_configs()[name]
    costs = hot_program_costs(config, **shape)
    violations, stale = check_budget(costs, budget)
    assert not violations, (
        "hot-program cost regression vs benchmarks/perf_budgets.json "
        "(intentional? rerun scripts/update_perf_budgets.py):\n  "
        + "\n  ".join(violations)
    )
    for msg in stale:
        import warnings

        warnings.warn(f"perf budget stale: {msg}")


def test_budget_gpt2_test():
    """Fast-tier leg of the net: the tiny config compiles in seconds, so the
    <5-min loop still exercises the full measure-and-compare path."""
    _assert_within_budget("gpt2_test")


@pytest.mark.slow
def test_budget_gpt2_test_cb():
    """The continuous-batching rollout programs: bucketed refill prefill +
    slot-refill segment decode (ops/slot_refill.py) — a lost logits-span
    restriction or a broken scatter shows up as a flop/byte jump here."""
    _assert_within_budget("gpt2_test_cb")


@pytest.mark.slow
def test_budget_gpt2_test_paged():
    """The paged-KV engine hot path (paged_refill + paged_decode,
    ops/paged_kv.py): the gather/scatter wrapped around the dense compute
    is itself under regression guard — a table-indexing change that blows
    up the gather (or quietly materializes the pool per step) shows up as
    a byte/temp jump here."""
    _assert_within_budget("gpt2_test_paged")


@pytest.mark.slow
def test_budget_gpt2_test_paged_kernel():
    """The in-place kernel decode path (paged_refill + paged_decode_kernel,
    ops/paged_attention.py, engine.decode_kernel: pallas): pins the
    program that contains NO per-segment dense-view gather/scatter — a
    change that reintroduces a pool-sized temporary shows up as a
    byte/temp jump. CPU-backend numbers lower the kernel through the
    Pallas interpreter (deterministic for the pinned toolchain)."""
    _assert_within_budget("gpt2_test_paged_kernel")


@pytest.mark.slow
def test_budget_gpt2_test_paged_prefill():
    """The fully in-place paged engine with chunked-prefill scheduling
    (paged_prefill_kernel + paged_prefill_chunk + paged_decode_kernel,
    ops/paged_prefill.py, engine.prefill_kernel: pallas +
    engine.prefill_chunk): pins the refill/chunk programs that contain NO
    dense-view gather/scatter — a change reintroducing a pool-sized
    temporary (or losing the chunk program's logits-span restriction)
    shows up as a byte/temp jump."""
    _assert_within_budget("gpt2_test_paged_prefill")


@pytest.mark.slow
def test_budget_gpt2_test_spec():
    """Speculative continuous batching (engine.speculative): the spec
    refill (target prefill through the block table + dense draft-cache
    prefill) and the speculative segment (draft-propose loop + ONE
    multi-position verify forward per round). The budget pins that the
    verify really is a single target forward over gamma+1 positions — a
    change that re-serializes verification (gamma+1 forwards) shows up as
    a flop jump, and speculation adds exactly these two programs per
    bucket (the zero-extra-programs claim).
    The serial `generate` budget here is the solo speculative sampler —
    the bit-parity reference program (tests/test_spec_engine.py)."""
    _assert_within_budget("gpt2_test_spec")


@pytest.mark.slow
def test_budget_ilql_gpt2_test():
    """ILQL's programs: twin-Q/CQL train step + the advantage-reshaping
    sampler (a different generate program than PPO's)."""
    _assert_within_budget("ilql_gpt2_test")


@pytest.mark.slow
def test_budget_sft_gpt2_test():
    _assert_within_budget("sft_gpt2_test")


@pytest.mark.slow
def test_budget_gpt2_small():
    """The flagship bench model (BASELINE.md): the exact programs whose
    samples/s the driver benchmark measures on chip."""
    _assert_within_budget("gpt2_small")


@pytest.mark.slow
def test_budget_gptj_6b_scan():
    """The large-model path: 6B with scan_layers + full remat, abstract
    weights (nothing materialized). Guards the remat/scan program structure
    the pod-scale story depends on — e.g. remat silently disabled shows up
    as a huge temp_bytes jump."""
    _assert_within_budget("gptj_6b_scan")


def test_budget_file_covers_matrix():
    """Every config in the guarded matrix has a committed budget with its
    trainer's full program set present — and no orphaned budgets survive a
    config rename (the generator preserves existing entries)."""
    from trlx_tpu.perf import budget_programs

    with open(BUDGET_PATH) as f:
        payload = json.load(f)
    expected = budget_programs()
    assert set(payload["budgets"]) == set(expected)
    for name, progs in expected.items():
        for prog in progs:
            entry = payload["budgets"][name][prog]
            assert entry["flops"] > 0 and entry["bytes_accessed"] > 0


@pytest.mark.slow
def test_budget_grpo_gpt2_test():
    """GRPO's programs: head-less policy generate, hydra-ref scoring, and
    the group-relative-advantage train step."""
    _assert_within_budget("grpo_gpt2_test")


@pytest.mark.slow
def test_budget_dpo_gpt2_test():
    """DPO's paired-completion logp train step."""
    _assert_within_budget("dpo_gpt2_test")


@pytest.mark.slow
def test_budget_ppo_t5_test():
    """The seq2seq leg: T5 encode/decode generate, teacher-forced scoring
    with the decoder hydra branch, and the seq2seq PPO step — abstract
    weights through build_seq2seq_lm."""
    _assert_within_budget("ppo_t5_test")


@pytest.mark.slow
def test_budget_gptj_6b_fsdp2_tp2_sp2():
    """The true SPMD program: 6B sharded over an 8-device fsdp2*tp2*sp2
    mesh with real param/optimizer/batch shardings attached — per-device
    cost and memory incl. the GSPMD-inserted collectives. A silently lost
    sharding shows up as a multi-x flop/temp jump."""
    _assert_within_budget("gptj_6b_fsdp2_tp2_sp2")


@pytest.mark.slow
def test_budget_neox_20b_tp4_ilql():
    """The megatron_20b-shaped ILQL programs (TP4 x fsdp2, seq 1024, int8
    Adam, bf16 params — the v4-16 capacity recipe) compile and stay within
    budget: the strongest hardware-free guard on the >20B-scale path the
    reference serves with NeMo (``megatron_20b.yaml:53-57``)."""
    _assert_within_budget("neox_20b_tp4_ilql")


def test_capacity_plan_tiny():
    """plan(): exact sharded weight/optimizer arithmetic + program costs,
    no weights materialized."""
    from trlx_tpu.perf import budget_configs, plan

    config, shape = budget_configs()["gpt2_test"]
    out = plan(config, **shape)
    assert out["n_params"] > 0
    # replicated over the dp-only mesh: per-device == full weight bytes
    assert out["per_device"]["param_bytes"] > 0
    assert out["per_device"]["optimizer_bytes"] > 0
    assert "train_step" in out["programs"]


@pytest.mark.slow
def test_capacity_plan_sharded_weights_shrink():
    """fsdp/tp sharding must reduce per-device weight bytes by the sharded
    axes' product (up to non-divisible leaves)."""
    from trlx_tpu.perf import budget_configs, plan

    dense, shape = budget_configs()["gptj_6b_scan"]
    sharded, shape_s = budget_configs()["gptj_6b_fsdp2_tp2_sp2"]
    # programs=() -> pure sharded-bytes arithmetic, no 6B compiles
    a = plan(dense, **shape, programs=())["per_device"]["param_bytes"]
    b = plan(sharded, **shape_s, programs=())["per_device"]["param_bytes"]
    # dense mesh is dp8 (replicated weights); sharded is fsdp2*tp2 -> ~4x less
    assert b < a / 3, (a, b)
