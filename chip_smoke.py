"""Does the system still start on the chip?

One process, one JAX initialization, nothing spawned. Drives PPO on a TPU
through the entry points a user calls — ``trlx_tpu.train()``, the paged
continuous-batching Engine, the HTTP serving frontend — at the full
published width of GPT-2-small (``builtin:gpt2-small``: 12 layers x 768,
12 heads x 64, vocab 50257; random init from the config seed,
``builtin:bytes`` tokenizer; 64-token prompts and 40 new tokens),
and checks what comes out by the repo's own means.

    python chip_smoke.py            # one chip: device, train, engine, kernels
    python chip_smoke.py --chips 4  # four chips: the sharded phase only

Every phase prints one JSON object; the LAST stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as JAX reports it. Exit code 0 only with ``"ok": true``. A
phase that raises ends the run with ``"ok": false`` and a non-zero code.
Where JAX finds no TPU the script fails at once and prints no result. The
one exception is a caller who pinned ``JAX_PLATFORMS=cpu``: that asks for
the CPU rehearsal, which walks every phase at ``builtin:gpt2-test`` size
with the kernels interpreted — and still ends ``"ok": false`` with a
non-zero code, because a CPU run proves nothing about the chip.

The seconds and bytes printed per phase are facts for whoever debugs the
next bring-up. They are smoke output, not benchmark results.
"""

import argparse
import contextlib
import dataclasses
import functools
import gc
import http.client
import json
import shutil
import statistics
import sys
import tempfile
import time
import traceback

import numpy as np

DEFAULT_PHASES = ("device", "train", "engine", "kernels")
CHIPS4_PHASES = ("sharded",)

# Pallas kernel flavors (trlx_tpu.analysis.kernels.KERNEL_PARITY) that Mosaic
# refuses today, with the compiler's words; tests/test_aot_tpu.py turns this
# table into strict xfails. Everything else is in KERNEL_CHECKS below.
KERNELS_REFUSED = {}


@dataclasses.dataclass(frozen=True)
class Size:
    """The shapes a run uses. Widths are the model's; only the rehearsal
    shrinks them."""

    model: str
    chunk: int  # rollouts per collection == train batch
    prompt: int
    new: int
    unfrozen: int
    heads: int
    head_dim: int
    vocab: int
    interpret: bool  # Pallas kernels under the interpreter (CPU rehearsal)


FULL = Size("builtin:gpt2-small", 128, 64, 40, 2, 12, 64, 50257, False)
TOY = Size("builtin:gpt2-test", 8, 16, 8, 1, 4, 16, 259, True)


def phase_table(chips: int):
    if chips == 1:
        return DEFAULT_PHASES
    if chips == 4:
        return CHIPS4_PHASES
    raise ValueError(f"--chips must be 1 or 4, got {chips}")


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------


def reward_fn(samples, prompts, outputs, **kwargs):
    return [float(sum(c in "aeiou" for c in o)) for o in outputs]


def make_prompts(size: Size, n: int, seed: int = 0):
    rng = np.random.RandomState(seed)
    return [
        "".join(chr(97 + c) for c in rng.randint(0, 26, size.prompt)) for _ in range(n)
    ]


def ppo_config(size: Size, ckpt_dir: str, total_steps: int, **parallel):
    """The smoke task shape: 64-token prompts, 40 new tokens,
    chunk 128, ppo_epochs 4, two unfrozen layers, default attention_impl —
    which on a TPU is the Pallas flash kernel. The rehearsal forces that
    kernel (interpreted) so the CPU walk takes the same path."""
    from trlx_tpu.data.default_configs import default_ppo_config

    extra = dict(attention_impl="pallas") if size.interpret else {}
    return default_ppo_config().evolve(
        train=dict(
            seq_length=size.prompt + size.new,
            batch_size=size.chunk,
            total_steps=total_steps,
            epochs=total_steps,  # never the binding limit
            eval_interval=1_000_000,
            checkpoint_interval=1_000_000,
            checkpoint_dir=ckpt_dir,
            tracker=None,
        ),
        model=dict(
            model_path=size.model,
            num_layers_unfrozen=size.unfrozen,
            model_extra_kwargs=extra,
        ),
        parallel=dict(dict(data=-1, fsdp=1, model=1), **parallel),
        method=dict(
            num_rollouts=size.chunk,
            chunk_size=size.chunk,
            ppo_epochs=4,
            gen_kwargs=dict(
                max_new_tokens=size.new, top_k=0, top_p=1.0, do_sample=True
            ),
        ),
    )


def build_trainer(config, prompts, prompt_len):
    """A trainer with its prompt pipeline, as ``trlx_tpu.train()`` builds it."""
    import trlx_tpu.pipeline.offline_pipeline  # noqa: F401  (registration)
    import trlx_tpu.trainer.ppo  # noqa: F401
    from trlx_tpu.pipeline import get_pipeline
    from trlx_tpu.trainer import get_trainer

    trainer = get_trainer(config.train.trainer)(
        config=config, reward_fn=reward_fn, metric_fn=None, stop_sequences=[]
    )
    trainer.add_prompt_pipeline(
        get_pipeline(config.train.pipeline)(prompts, prompt_len, trainer.tokenizer)
    )
    return trainer


class Recorder:
    """Stands in for the tracker: keeps every stats record the run logs."""

    def __init__(self):
        self.records = []

    def log(self, stats, step=None):
        self.records.append(dict(stats))

    def finish(self):
        pass


def abstract(tree):
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=getattr(x, "sharding", None)
        ),
        tree,
    )


def first_batch(trainer, size: Size):
    loader = trainer.store.create_loader(
        size.chunk, shuffle=False, query_length=size.prompt, response_length=size.new
    )
    return next(iter(loader))


def compiled_texts(trainer, size: Size, batch):
    """Compiled text of the exact train-step and scoring programs the
    trainer ran (same jitted functions, abstract twins of the live
    arguments; with the persistent cache on this is a cache hit)."""
    from trlx_tpu.parallel.sharding import shard_batch

    items = batch._asdict()
    arrays = shard_batch(
        {k: v for k, v in items.items() if hasattr(v, "ndim")}, trainer.mesh
    )
    train_text = (
        trainer._train_step_fn.lower(
            abstract(trainer.state), abstract(arrays), np.float32(1.0)
        )
        .compile()
        .as_text()
    )
    B, P, N = size.chunk, size.prompt, size.new
    score_in = shard_batch(
        {
            "sequences": np.zeros((B, P + N), np.int32),
            "prompt_mask": np.zeros((B, P), np.int32),
            "response_tokens": np.zeros((B, N), np.int32),
            "response_mask": np.zeros((B, N), np.int32),
        },
        trainer.mesh,
    )
    score_text = (
        trainer._get_score_fn((B, P, N))
        .lower(
            abstract(trainer.state.params),
            abstract(trainer.ref_params),
            *(abstract(score_in[k]) for k in
              ("sequences", "prompt_mask", "response_tokens", "response_mask")),
        )
        .compile()
        .as_text()
    )
    return train_text, score_text


def check_store(trainer, size: Size, n: int):
    """The rollouts a trainer collected: all there, finite, near full
    length (a random-init policy all but never samples eos)."""
    elems = list(trainer.store.history)[-n:]
    assert len(elems) == n, f"store holds {len(elems)} rollouts, wanted {n}"
    lens = [len(e.response_tensor) for e in elems]
    for e in elems:
        for name in ("logprobs", "values", "rewards"):
            assert np.isfinite(getattr(e, name)).all(), f"non-finite {name}"
    assert min(lens) >= 1 and max(lens) <= size.new
    assert np.mean(lens) >= 0.9 * size.new, f"short rollouts: mean {np.mean(lens)}"
    return {"rollouts": n, "gen_len_min": int(min(lens)), "gen_len_mean": float(np.mean(lens))}


def loss_stats(stats):
    out = {k: float(v) for k, v in stats.items() if k.startswith("losses/")}
    assert out, "no losses/* in the step stats"
    for k, v in out.items():
        assert np.isfinite(v), f"{k} = {v}"
    return out


def bytes_in_use(devices):
    stats = [d.memory_stats() for d in devices]
    return None if stats[0] is None else [int(m["bytes_in_use"]) for m in stats]


def peak_bytes(device):
    stats = device.memory_stats()  # None where the backend has none (CPU)
    return None if stats is None else int(stats["peak_bytes_in_use"])


# ---------------------------------------------------------------------------
# phases (one chip)
# ---------------------------------------------------------------------------


def phase_device(size: Size, devices):
    import jax
    import jaxlib

    from trlx_tpu import native

    d = devices[0]
    out = {
        "platform": d.platform,
        "device_kind": d.device_kind,
        "count": len(devices),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        # trlx_tpu.native compiles host_runtime.cpp at first use and falls
        # back to numpy silently when it cannot; say which
        "native_host_runtime": "compiled" if native.available() else "numpy fallback",
    }
    if d.platform == "tpu":
        from trlx_tpu.observability.metrics import device_peak_flops

        out["peak_flops_table"] = device_peak_flops(d)  # raises on an unknown kind
    return out


def phase_train(size: Size, devices):
    """``trlx_tpu.train()``: two collections, eight optimizer steps, the
    evaluate() program (learn() runs it before the first and after the last
    step)."""
    import jax
    import jax.numpy as jnp

    import trlx_tpu

    ckpt = tempfile.mkdtemp(prefix="chip_smoke_train_")
    recorder = Recorder()
    before = {}

    def hook(trainer):
        trainer.tracker = recorder
        before["params"] = jax.tree_util.tree_map(jnp.copy, trainer.state.params)

    t0 = time.perf_counter()
    try:
        trainer = trlx_tpu.train(
            reward_fn=reward_fn,
            prompts=make_prompts(size, 4 * size.chunk),
            config=ppo_config(size, ckpt, total_steps=8),
            init_trainer_hook=hook,
        )
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    wall = time.perf_counter() - t0

    steps = [r for r in recorder.records if "time/train_step" in r]
    collects = [r for r in recorder.records if "time/exp" in r]
    assert len(steps) == 8, f"{len(steps)} optimizer steps, wanted 8"
    assert len(collects) == 2, f"{len(collects)} collections, wanted 2"
    losses = [loss_stats(r) for r in steps]

    changed = [
        bool(jnp.any(a != b))
        for a, b in zip(
            jax.tree_util.tree_leaves(before["params"]),
            jax.tree_util.tree_leaves(trainer.state.params),
        )
    ]
    assert any(changed), "no parameter changed in eight steps"

    snap = trainer.obs.metrics.snapshot(reset_histograms=False)
    recompiles = {k: v for k, v in snap.items() if k.startswith("recompile/")}
    assert not any(recompiles.values()), f"warm programs recompiled: {recompiles}"

    train_text, score_text = compiled_texts(trainer, size, first_batch(trainer, size))
    kernel_in = {
        "train_step": "tpu_custom_call" in train_text,
        "score": "tpu_custom_call" in score_text,
    }
    if not size.interpret:
        assert all(kernel_in.values()), f"flash kernel missing: {kernel_in}"

    step_s = [float(r["time/train_step"]) for r in steps]
    collect_s = [float(r["time/exp"]) for r in collects]
    steady = statistics.median(step_s[1:])
    return {
        "model": size.model,
        "steps": len(steps),
        "collections": len(collects),
        "losses_first": losses[0],
        "losses_last": losses[-1],
        "param_leaves_changed": f"{sum(changed)}/{len(changed)}",
        "recompiles": recompiles,
        "tpu_custom_call_in": kernel_in,
        "wall_s": wall,
        # first call of each program compiles; the rest are steady
        "collect_s": collect_s,
        "train_step_s": step_s,
        "steady_step_s": steady,
        "compile_s": {
            "collect": collect_s[0] - collect_s[1],
            "train_step": step_s[0] - steady,
        },
        "peak_bytes_in_use": peak_bytes(devices[0]),
        **check_store(trainer, size, size.chunk),
    }


def http_generate(port: int, prompt_ids, seed: int):
    """One streamed request against /v1/generate, read to its done frame."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request(
            "POST",
            "/v1/generate",
            json.dumps(
                {"prompt_ids": prompt_ids, "seed": seed, "stream": True,
                 "class": "interactive"}
            ),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        status, raw = resp.status, resp.read().decode()
    finally:
        conn.close()
    streamed, done = 0, None
    for frame in raw.split("\n\n"):
        if not frame.startswith("data: "):
            continue
        payload = json.loads(frame[len("data: "):])
        if "tokens" in payload:
            streamed += len(payload["tokens"])
        elif payload.get("done"):
            done = payload
    return status, streamed, done


def phase_engine(size: Size, devices):
    """One collection through the paged continuous-batching Engine with the
    prefix cache (XLA kernels — the path the rollout/serve cells will sit
    on), then three streamed HTTP requests against the serving frontend on
    the same Engine programs."""
    ckpt = tempfile.mkdtemp(prefix="chip_smoke_engine_")
    serve_new = 8
    config = ppo_config(size, ckpt, total_steps=8).evolve(
        train=dict(continuous_batching=True),
        engine=dict(backend="paged", prefix_cache=True),
        serve=dict(
            enabled=True, host="127.0.0.1", port=0, slots=2,
            max_new_tokens=serve_new,
        ),
    )
    trainer = build_trainer(config, make_prompts(size, 4 * size.chunk), size.prompt)
    try:
        t0 = time.perf_counter()
        trainer.make_experience(size.chunk)
        collect_s = time.perf_counter() - t0
        out = check_store(trainer, size, size.chunk)
        stats = trainer.make_experience_stats
        assert stats.get("engine/decode_kernel_pallas", 0.0) == 0.0
        trainer._maybe_start_serving()
        requests = []
        for i in range(3):
            t0 = time.perf_counter()
            status, streamed, done = http_generate(
                trainer._serve.port, list(range(5 + i, 21 + i)), seed=i
            )
            assert status == 200, f"request {i}: HTTP {status}"
            assert done is not None, f"request {i}: no done frame"
            assert 0 < done["n_tokens"] <= serve_new, done
            assert streamed == done["n_tokens"], (streamed, done)
            requests.append(
                {"status": status, "tokens": streamed,
                 "seconds": time.perf_counter() - t0}
            )
    finally:
        trainer._shutdown_collectors()
        shutil.rmtree(ckpt, ignore_errors=True)
    return {
        "collect_s": collect_s,
        "slot_utilization": stats.get("throughput/slot_utilization"),
        "prefix_hit_rate": stats.get("engine/prefix_hit_rate"),
        "kv_blocks_in_use": stats.get("engine/kv_blocks_in_use"),
        "requests": requests,
        "peak_bytes_in_use": peak_bytes(devices[0]),
        **out,
    }


# --- kernels: each flavor that compiles, against its registered reference ---


def _attention_inputs(size: Size, seed: int):
    import jax
    import jax.numpy as jnp

    B, T = 8, size.prompt + size.new
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = (B, T, size.heads, size.head_dim)
    q, k, v, do = (jax.random.normal(key, shape, jnp.bfloat16) for key in ks)
    mask = np.ones((B, T), np.float32)
    mask[:, :3] = 0.0  # left padding, ragged across the batch
    mask[0, :7] = 0.0
    return q, k, v, do, jnp.asarray(mask)


def _k_flash_fwd(size: Size):
    from trlx_tpu.ops.flash_attention import attention_reference, flash_attention

    q, k, v, _, mask = _attention_inputs(size, 1)
    out = flash_attention(q, k, v, mask, interpret=size.interpret)
    ref, _ = attention_reference(q, k, v, mask)
    valid = np.asarray(mask) > 0  # fully-masked query rows hold sentinels
    return np.asarray(out, np.float32)[valid], np.asarray(ref, np.float32)[valid]


def _k_flash_bwd(size: Size):
    import jax
    import jax.numpy as jnp

    from trlx_tpu.ops.flash_attention import attention_reference, flash_attention

    q, k, v, do, mask = _attention_inputs(size, 2)
    w = (do.astype(jnp.float32) * mask[:, :, None, None])

    def through(fn):
        return jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w),
            argnums=(0, 1, 2),
        )(q, k, v)

    got = through(lambda q, k, v: flash_attention(q, k, v, mask, interpret=size.interpret))
    ref = through(lambda q, k, v: attention_reference(q, k, v, mask)[0])
    flat = lambda gs: np.concatenate([np.asarray(g, np.float32).ravel() for g in gs])
    return flat(got), flat(ref)


def _pool_inputs(size: Size, T: int, seed: int):
    """Random block pool + per-row tables + an additive bias masking a
    ragged tail (stale pool values behind the mask must contribute 0)."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.ops.paged_kv import num_table_blocks

    B, S, bs = 8, size.prompt + size.new, 16
    TB = num_table_blocks(S, bs)
    NB = 1 + B * TB
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q_shape = (B, size.heads, size.head_dim) if T == 0 else (B, T, size.heads, size.head_dim)
    q = jax.random.normal(ks[0], q_shape, jnp.bfloat16)
    pool = (NB, bs, size.heads, size.head_dim)
    k_pool = jax.random.normal(ks[1], pool, jnp.bfloat16)
    v_pool = jax.random.normal(ks[2], pool, jnp.bfloat16)
    table = jnp.asarray(1 + np.arange(B * TB, dtype=np.int32).reshape(B, TB))
    lens = S - np.arange(B)  # visible columns per row, ragged
    cols = np.arange(S)[None, :] < lens[:, None]  # [B, S]
    if T == 0:
        visible = cols[:, None, :]  # [B, 1, S]
    else:  # query t of a T-span ending at the row's length sees cols <= its own
        q_col = (lens[:, None] - T + np.arange(T)[None, :])[:, :, None]
        visible = (np.arange(S)[None, None, :] <= q_col)[:, None]  # [B, 1, T, S]
    bias = jnp.asarray(np.where(visible, 0.0, -1e9).astype(np.float32))
    return q, k_pool, v_pool, table, bias


def _k_paged_decode(size: Size):
    from trlx_tpu.ops.paged_attention import (
        paged_attention_decode,
        paged_attention_decode_reference,
    )

    args = _pool_inputs(size, 0, 3)
    return (
        np.asarray(paged_attention_decode(*args, interpret=size.interpret), np.float32),
        np.asarray(paged_attention_decode_reference(*args), np.float32),
    )


def _k_paged_prefill(size: Size):
    from trlx_tpu.ops.paged_prefill import (
        paged_prefill_attention,
        paged_prefill_attention_reference,
    )

    args = _pool_inputs(size, size.prompt, 4)
    return (
        np.asarray(paged_prefill_attention(*args, interpret=size.interpret), np.float32),
        np.asarray(paged_prefill_attention_reference(*args), np.float32),
    )


def _k_paged_verify(size: Size):
    from trlx_tpu.ops.paged_attention import paged_verify_attention
    from trlx_tpu.ops.paged_prefill import paged_prefill_attention_reference

    args = _pool_inputs(size, 5, 5)  # draft_gamma 4 + the re-fed token
    return (
        np.asarray(paged_verify_attention(*args, interpret=size.interpret), np.float32),
        np.asarray(paged_prefill_attention_reference(*args), np.float32),
    )


def _k_fused_sample(size: Size):
    """Unfiltered sampling at temperature 1 (the task's gen_kwargs; top-k and
    top-p stay on the XLA sampler: Mosaic lowers neither top_k nor sort).
    The draw must be the reference's draw; the logprob is compared."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.ops.paged_attention import sample_token_fused
    from trlx_tpu.ops.sampling import (
        GenerationConfig,
        per_row_keys,
        sample_token_from_logits,
    )

    B = 8
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(6), (B, size.vocab), jnp.float32)
    keys = per_row_keys(jax.random.PRNGKey(7), B)
    cfg = GenerationConfig(
        max_new_tokens=size.new, temperature=1.0, top_k=0, top_p=1.0,
        do_sample=True, per_row_rng=True,
    )
    step = jnp.zeros((B,), jnp.int32)
    tok, lp = sample_token_fused(logits, {}, keys, cfg, step, interpret=size.interpret)
    rtok, rlp = sample_token_from_logits(logits, {}, keys, cfg, step, None)
    assert np.array_equal(np.asarray(tok), np.asarray(rtok)), (tok, rtok)
    return np.asarray(lp), np.asarray(rlp)


def _k_kda_scan(size: Size):
    """The chunked delta rule at the published head size: 2 rows of 200
    tokens (a last chunk that is not whole), 2 heads of 128, from a non-zero
    state; float32 throughout, so the kernel's six-pass products must land
    on the ``jax.numpy`` form's. ``kda_chunked`` reads the backend itself."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.ops.delta_rule import kda_chunked, kda_chunked_reference

    ks = jax.random.split(jax.random.PRNGKey(8), 6)
    shape = (2, 200, 2, 128)
    q, k, v = (jax.random.normal(key, shape, jnp.float32) for key in ks[:3])
    q, k = q / jnp.linalg.norm(q, axis=-1, keepdims=True), k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.exp(jax.random.uniform(ks[3], shape, minval=np.log(1e-3), maxval=np.log(1.6)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
    args = (q, k, v, g, beta, jax.random.normal(ks[5], (2, 2, 128, 128)))
    flat = lambda out: np.concatenate([np.asarray(a, np.float32).ravel() for a in out])
    return flat(jax.jit(kda_chunked)(*args)), flat(jax.jit(kda_chunked_reference)(*args))


# flavor -> (check, tolerance relative to max(1, max|reference|)). bf16
# kernels against bf16/f32 references: a few output roundings (2^-8 each).
KERNEL_CHECKS = {
    "flash-fwd": (_k_flash_fwd, 2e-2),
    "flash-bwd": (_k_flash_bwd, 3e-2),
    "paged-decode": (_k_paged_decode, 2e-2),
    "paged-prefill": (_k_paged_prefill, 2e-2),
    "paged-verify": (_k_paged_verify, 2e-2),
    "fused-sample": (_k_fused_sample, 1e-4),
    "kda-scan": (_k_kda_scan, 1e-4),
}


def phase_kernels(size: Size, devices):
    from trlx_tpu.analysis.kernels import KERNEL_PARITY

    registered = {row[0] for row in KERNEL_PARITY}
    assert set(KERNEL_CHECKS) | set(KERNELS_REFUSED) == registered, registered
    out = {}
    for flavor, (check, tol) in KERNEL_CHECKS.items():
        got, ref = check(size)
        assert np.isfinite(got).all(), f"{flavor}: non-finite output"
        err = float(np.max(np.abs(got - ref)))
        scale = max(1.0, float(np.max(np.abs(ref))))
        out[flavor] = {"max_abs_err": err, "ref_max_abs": scale, "tol": tol * scale}
        assert err <= tol * scale, f"{flavor}: max abs err {err} > {tol * scale}"
    for flavor, words in KERNELS_REFUSED.items():
        out[flavor] = {"refused": words}
    return out


# ---------------------------------------------------------------------------
# the sharded phase (--chips 4)
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def one_device_mesh(device):
    """Trainers lay their mesh over every device JAX has; the comparison
    run wants exactly one of the four, so the constructor's ``make_mesh`` is
    handed that device for the duration (steered here, not by an option of
    the program)."""
    import trlx_tpu.trainer.base as base

    full = base.make_mesh
    base.make_mesh = functools.partial(full, devices=[device])
    try:
        yield
    finally:
        base.make_mesh = full


def score_batch(trainer, size: Size, batch):
    """The scoring forward on a given rollout batch."""
    import jax

    B, P, N = size.chunk, size.prompt, size.new
    out = trainer._dispatch_score(
        (B, P, N),
        np.concatenate([batch.query_tensors, batch.response_tensors], axis=1),
        np.asarray(batch.query_mask),
        np.asarray(batch.response_tensors),
        np.asarray(batch.response_mask),
    )
    return {k: np.asarray(v, np.float32) for k, v in jax.device_get(out).items()}


def phase_sharded(size: Size, devices):
    """The same PPO config on a one-device mesh and on ``fsdp=2, model=2``
    (the factorization ``__graft_entry__`` picks for n=4), one after the
    other in this process: same seed, same initial params, and — since
    sampling may diverge between layouts on one flipped token — the
    one-device run's first rollout batch fed to both. Scoring logprobs and
    first-step losses must agree to 1e-3 (the cross-topology bound of
    docs/RESILIENCE.md).

    Both layouts compute in float32 at the highest matmul precision here,
    because nothing less can be held to 1e-3 per token: the scoring forward
    emits logprobs in the compute dtype (bf16 is spaced 2^-5 apart at
    |logprob| in [4, 8)), and at default precision a TPU rounds matmul
    operands to bf16 even for f32 arrays, which amplifies the layouts'
    reduction-order noise to about 1e-2 per token over twelve layers
    (measured: 8e-3, the first four-chip run of PR 22). The layouts,
    shardings, collectives and the shard_map around the kernel do not depend
    on dtype or precision; bf16 under this mesh is what
    tests/test_aot_tpu.py compiles and bf16 on one chip is what the train
    phase executes."""
    import jax

    assert len(devices) == 4, f"--chips 4 needs four devices, JAX has {len(devices)}"
    with jax.default_matmul_precision("highest"):
        return _sharded(size, devices)


def _sharded(size: Size, devices):
    import jax

    tol = 1e-3
    f32 = dict(compute_dtype="float32")
    prompts = make_prompts(size, 4 * size.chunk)
    ckpt = tempfile.mkdtemp(prefix="chip_smoke_sharded_")

    def collect_and_step(trainer, batch=None):
        """One collection + four steps, all four on ``batch``."""
        trainer.make_experience(size.chunk)
        own = check_store(trainer, size, size.chunk)
        if batch is None:
            batch = first_batch(trainer, size)
        scores = score_batch(trainer, size, batch)
        t = []
        first = None
        for _ in range(4):
            t0 = time.perf_counter()
            stats = jax.device_get(trainer.train_step(batch))
            jax.block_until_ready(trainer.state.params)
            t.append(time.perf_counter() - t0)
            trainer.iter_count += 1
            first = first or loss_stats(stats)
        return batch, scores, first, own, t

    try:
        with one_device_mesh(devices[0]):
            solo = build_trainer(
                ppo_config(size, ckpt, 8, data=1, fsdp=1, model=1, **f32),
                prompts, size.prompt,
            )
        init = jax.device_get(solo.state.params)
        batch, solo_scores, solo_loss, solo_own, solo_t = collect_and_step(solo)
        solo._shutdown_collectors()
        del solo
        gc.collect()
        in_use_before = bytes_in_use(devices)

        mesh4 = build_trainer(
            ppo_config(size, ckpt, 8, data=1, fsdp=2, model=2, **f32),
            prompts, size.prompt,
        )
        assert dict(mesh4.mesh.shape)["fsdp"] == 2 and dict(mesh4.mesh.shape)["model"] == 2
        for a, b in zip(
            jax.tree_util.tree_leaves(init),
            jax.tree_util.tree_leaves(jax.device_get(mesh4.state.params)),
        ):
            assert np.array_equal(np.asarray(a), np.asarray(b)), "initial params differ"
        del init
        _, mesh_scores, mesh_loss, mesh_own, mesh_t = collect_and_step(mesh4, batch)

        valid = np.asarray(batch.response_mask) > 0
        score_err = {
            k: float(np.max(np.abs(mesh_scores[k] - solo_scores[k])[valid]))
            for k in solo_scores
        }
        loss_err = {k: abs(mesh_loss[k] - solo_loss[k]) for k in solo_loss}
        assert max(score_err.values()) <= tol, f"scoring differs: {score_err}"
        assert max(loss_err.values()) <= tol, f"first-step losses differ: {loss_err}"

        # every device holds a shard, some leaf is really split, and no
        # device carries the run alone
        held, split = set(), 0
        for leaf in jax.tree_util.tree_leaves(mesh4.state.params):
            held |= {s.device for s in leaf.addressable_shards}
            split += leaf.addressable_shards[0].data.shape != leaf.shape
        assert held == set(devices), f"params live on {held}"
        assert split > 0, "no parameter is partitioned"
        in_use = bytes_in_use(devices)
        added = None  # what the sharded trainer put on each device
        if in_use is not None:
            added = [now - was for now, was in zip(in_use, in_use_before)]
            assert 0 < max(added) <= 2 * min(added), f"uneven device memory: {added}"

        train_text, score_text = compiled_texts(mesh4, size, batch)
        collectives = {
            op: train_text.count(f" {op}(")
            for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                       "collective-permute")
        }
        assert collectives["all-reduce"] + collectives["reduce-scatter"] > 0, collectives
        assert collectives["all-gather"] > 0, collectives  # fsdp weight gathers
        kernel_in = {
            "train_step": "tpu_custom_call" in train_text,
            "score": "tpu_custom_call" in score_text,
        }
        if not size.interpret:
            assert all(kernel_in.values()), f"flash kernel missing: {kernel_in}"
        mesh4._shutdown_collectors()
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    return {
        "mesh": {k: v for k, v in dict(mesh4.mesh.shape).items() if v > 1},
        "tol": tol,
        "score_max_abs_err": score_err,
        "first_step_loss_abs_err": loss_err,
        "losses_one_device": solo_loss,
        "losses_mesh": mesh_loss,
        "rollouts_one_device": solo_own,
        "rollouts_mesh": mesh_own,
        "train_step_s_one_device": solo_t,
        "train_step_s_mesh": mesh_t,
        "param_leaves_split": split,
        "bytes_in_use": in_use,
        "bytes_in_use_before_mesh_run": in_use_before,
        "bytes_in_use_added_by_mesh_run": added,
        "collectives_in_train_step": collectives,
        "tpu_custom_call_in": kernel_in,
    }


PHASES = {
    "device": phase_device,
    "train": phase_train,
    "engine": phase_engine,
    "kernels": phase_kernels,
    "sharded": phase_sharded,
}


def run(phases, size: Size, devices, rehearsal: bool) -> int:
    """Run ``phases`` in order, one JSON line each; stop at the first that
    raises. The last line is the contract's object."""
    failed = None
    for name in phases:
        t0 = time.perf_counter()
        try:
            out = PHASES[name](size, devices)
        except Exception as e:  # reported as a failed run, never swallowed
            traceback.print_exc()
            out, failed = {"error": f"{type(e).__name__}: {e}"}, name
        print(
            json.dumps(
                {"phase": name, "ok": failed is None,
                 "seconds": time.perf_counter() - t0, **out}
            ),
            flush=True,
        )
        if failed:
            break
    d = devices[0]
    last = {
        # a CPU rehearsal is never ok: it proves nothing about the chip
        "ok": failed is None and not rehearsal,
        "device": {"platform": d.platform, "kind": d.device_kind, "count": len(devices)},
    }
    if failed:
        last["failed"] = failed
    if rehearsal:
        last["rehearsal"] = "passed" if failed is None else "failed"
    print(json.dumps(last), flush=True)
    return 0 if last["ok"] else 1


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--chips", type=int, default=1,
        help="1: the default phases on one chip; 4: the sharded phase only",
    )
    args = parser.parse_args(argv)
    phases = phase_table(args.chips)

    from trlx_tpu.trlx import initialize_runtime, measurement_devices

    initialize_runtime()
    devices, rehearsal = measurement_devices()  # raises where there is no TPU
    return run(phases, TOY if rehearsal else FULL, devices, rehearsal)


if __name__ == "__main__":
    sys.exit(main())
