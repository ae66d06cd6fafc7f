"""The unified generation Engine: one interface over the repo's generation
paths, backed by a dense per-slot KV cache or the paged block pool.

Three generation paths used to live inside trainers: serial ``generate``
(ops/sampling.py), the PR-2 rollout pipeline (host overlap of an unchanged
serial decode), and the PR-3 slot-refill continuous-batching engine
(pipeline/continuous_batching.py). This module is their common home:

- :class:`SerialEngine` — plain batch generate behind the Engine
  interface. The dense serial path itself is untouched (it is the
  bit-equivalence reference every other path is tested against).
- :class:`ContinuousEngine` — the slot-refill engine (queue → refill →
  segment decode → harvest), generalized over the KV backend:

  * **dense** (default): the PR-3 per-slot ``[B, S]`` cache, byte-for-byte.
  * **paged** (``fns.paged`` set): KV lives in a block pool with per-slot
    block tables (``ops/paged_kv.py``). This engine owns the host half:
    a refcounted :class:`~trlx_tpu.engine.allocator.BlockAllocator` and
    lazy per-segment growth, so the pool's high-water tracks *live
    tokens* instead of ``slots × max_length``; and optionally a
    :class:`~trlx_tpu.engine.prefix_cache.PrefixCache` so rows whose
    padded prompts share committed full blocks prefill only their
    unshared suffix (GRPO groups, repeated eval prompts).

Determinism and bit-parity are inherited from the device half
(``ops/slot_refill.py``): prompts are assigned to slots in submission
order, harvested in slot order, and every sequence's tokens / logprobs /
values / mask are bit-identical to plain ``generate`` under per-row RNG —
for the dense AND paged backends, with and without prefix hits
(``tests/test_engine.py``, ``tests/test_continuous_batching.py``).

Utilization accounting (docs/PERFORMANCE.md): every decode step costs
``B`` slot-steps on device; only live slots produce real tokens.
``slot_utilization`` = live ÷ total slot-steps; ``padded_decode_frac`` is
its complement. The paged backend adds block-pool and prefix-cache gauges
(``engine/*``, ``memory/kv_cache_bytes``) — registered in
``tests/test_metric_names.py``.

Thread affinity: engines are single-threaded by design — exactly ONE
thread of control calls ``enqueue_prompts``/``step`` over an engine's
lifetime (the trainer's main thread, or the serve pump thread that owns a
serving engine exclusively — ``trlx_tpu/serve/server.py``); the rollout
pipeline worker and the HTTP handler threads see nothing but harvested
numpy copies handed over through locked serve-side buffers. If shared
mutable state is ever introduced here, annotate it ``# guarded-by:
<lock>`` so graftlint's lock-discipline pass (docs/STATIC_ANALYSIS.md)
enforces the locking, as in ``rollout_pipeline.py``.

Serving extensions (docs/SERVING.md): requests carry an optional tenant
(prefix-cache namespace + allocator quota) and a priority class —
``interactive`` outranks ``eval`` outranks ``actor`` at admission, and
queued higher-class traffic preempts still-prefilling lower-class slots
at step boundaries (the chunked-prefill scheduler is the seam: committed
prompt chunks are inserted into the tenant's radix chain before the slot
is vacated, so preempted work re-lands as prefix hits). An attached
:class:`~trlx_tpu.serve.tiering.HostTier` re-lands evicted prefix blocks
from host RAM instead of re-prefilling them.
"""

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from trlx_tpu.engine.allocator import (
    BlockAllocator,
    BlockPoolExhausted,
    TenantQuotaExceeded,
)
from trlx_tpu.engine.prefix_cache import PrefixCache
from trlx_tpu.ops import cache_layout
from trlx_tpu.ops.paged_kv import block_bytes, kv_bytes, num_table_blocks

__all__ = [
    "CompletedSequence",
    "EngineStats",
    "Engine",
    "SerialEngine",
    "ContinuousEngine",
    "SERVE_CLASSES",
]

# Priority classes, best-first (docs/SERVING.md): interactive user traffic
# outranks eval sweeps outranks the trainer's own actor batches. Admission
# pops the best-ranked queued request (FIFO within a class by submission
# index), so the rank table IS the scheduling policy.
SERVE_CLASSES = ("interactive", "eval", "actor")
_CLASS_RANK = {k: i for i, k in enumerate(SERVE_CLASSES)}
_DEFAULT_RANK = _CLASS_RANK["actor"]


@dataclass
class CompletedSequence:
    """One finished rollout, harvested from its slot."""

    index: int  # global submission index (queue order)
    prompt_ids: np.ndarray  # [P] left-padded prompt
    prompt_mask: np.ndarray  # [P]
    tokens: np.ndarray  # [N] response tokens (pad after eos)
    logprobs: np.ndarray  # [N] behavior logprobs
    values: np.ndarray  # [N] value-head outputs (0 if no head)
    mask: np.ndarray  # [N] 1 on real response tokens (incl. eos)
    meta: Any = None  # caller payload (e.g. GRPO group id)
    # request lifecycle timestamps (perf_counter; 0.0 = untracked): the
    # per-request spans the serve SLO metrics derive queue-wait/TTFT/TPOT
    # from (trlx_tpu/serve/metrics.py) — same instants the tracer's
    # engine/queue_wait → prefill → decode spans are built on
    t_enqueue: float = 0.0
    t_prefill0: float = 0.0
    t_prefill1: float = 0.0
    t_harvest: float = 0.0


@dataclass
class _Request:
    index: int
    input_ids: np.ndarray  # [P]
    attention_mask: np.ndarray  # [P]
    key: np.ndarray  # [2] per-row RNG chain start
    meta: Any = None
    # lifecycle timestamps (perf_counter) for the per-request trace spans:
    # queue wait = enqueue → first prefill work, prefill = the refill (or
    # first-through-last chunk) program calls, decode = prefill end →
    # harvest
    t_enqueue: float = 0.0
    t_refill0: float = 0.0
    t_refill1: float = 0.0
    # chunked prefill: next prompt column to prefill (None = prefill done
    # or not chunked); the engine advances one chunk per step
    prefill_pos: Optional[int] = None
    # serving extensions: prefix-cache namespace + quota identity, and the
    # priority class admission/preemption schedule on (docs/SERVING.md)
    tenant: Optional[str] = None
    klass: str = "actor"


@dataclass
class EngineStats:
    """Aggregate slot / block / prefix accounting over one engine lifetime."""

    segments: int = 0
    decode_steps: int = 0  # device decode steps executed
    slot_steps: int = 0  # decode_steps × B
    live_slot_steps: int = 0  # slot-steps spent on live rows
    refill_prefills: int = 0  # refill-program invocations
    refilled_rows: int = 0  # prompts placed into slots
    harvested: int = 0
    decode_s: float = 0.0  # wall time inside decode segments
    refill_s: float = 0.0  # wall time inside refill prefills
    queue_wait_s: float = 0.0  # summed enqueue→refill wait over requests
    # per-request queue waits (one sample per admitted request): the
    # p50/p95 the trainer gauges and the serve SLO metrics share — the
    # aggregate sum above cannot answer "how long does a request wait",
    # which is the admission-control question (docs/SERVING.md)
    queue_wait_samples: List[float] = field(default_factory=list)
    # KV memory (docs/PERFORMANCE.md): the persistent cache allocation, and
    # for the paged backend the live-token-scaled high-water
    kv_cache_bytes: int = 0  # dense cache / paged pool allocation
    kv_blocks_total: int = 0  # 0 = dense backend
    kv_blocks_in_use: int = 0  # high-water blocks simultaneously held
    kv_bytes_high_water: int = 0  # blocks_in_use × per-block bytes (paged)
    # paged decode compute path: True = in-place Pallas kernel decode
    # (engine.decode_kernel: pallas), False = the gather/scatter reference
    decode_kernel_pallas: bool = False
    # paged prefill compute path: True = in-place Pallas prefill kernel
    # (engine.prefill_kernel: pallas), False = gather-prefill-scatter
    prefill_kernel_pallas: bool = False
    # analytic bytes the refill prefills move through transient dense
    # views: gather = pool → dense view on program entry, scatter = written
    # span → pool on exit. Exactly 0 under the in-place prefill kernel —
    # the acceptance number of the ENGINE_PREFILL A/B (docs/PERFORMANCE.md)
    refill_gather_bytes: int = 0
    refill_scatter_bytes: int = 0
    # chunked-prefill scheduling (engine.prefill_chunk)
    prefill_chunk_calls: int = 0  # mid-chunk program invocations
    # decode-stall accounting: wall-seconds of prefill work that ran while
    # >= 1 seeded (decoding) slot sat waiting — one sample per stalling
    # prefill event, so p50/p95/max bound how long a live decode slot can
    # be held up by prompt admission (the number chunked prefill shrinks)
    decode_stall_s: float = 0.0
    decode_stall_samples: List[float] = field(default_factory=list)
    # prefix cache
    prefix_enabled: bool = False
    prefix_lookup_blocks: int = 0
    prefix_hit_blocks: int = 0
    prefix_tokens_saved: int = 0  # prompt columns NOT re-prefilled
    prefix_evicted_blocks: int = 0
    prefill_tokens: int = 0  # prompt columns actually prefilled
    # host-RAM tiering (trlx_tpu/serve/tiering.py): evicted prefix blocks
    # re-landed from the host pool instead of re-prefilled
    host_tier_enabled: bool = False
    host_tier_hit_blocks: int = 0
    host_tier_tokens_saved: int = 0  # prompt columns re-landed, not computed
    # priority scheduling: still-prefilling lower-class slots vacated for
    # queued higher-class traffic (requeued, committed chunks preserved)
    preempted_rows: int = 0
    # speculative decode segments (engine.speculative = k > 0): deltas of
    # the device-cumulative spec counters over this collection — verify
    # rounds run, live row-rounds, draft tokens accepted, tokens committed
    spec_gamma: int = 0
    spec_rounds: int = 0
    spec_live_rounds: int = 0
    spec_accepted: int = 0
    spec_committed: int = 0
    # spec verify compute path: True = in-place multi-position verify
    # kernel (engine.decode_kernel: pallas with engine.speculative),
    # False = the gather → shared round → scatter reference
    spec_verify_kernel_pallas: bool = False
    # harvest-side generation canary (observability/health.py gen_canary):
    # per-sequence generated lengths, and adjacent repeated-token pairs —
    # the cheap on-harvest signal for degenerate looping generations
    gen_len_samples: List[float] = field(default_factory=list)
    repeat_pairs: int = 0  # adjacent equal-token pairs in responses
    repeat_pairs_total: int = 0  # adjacent in-response pairs observed

    @property
    def slot_utilization(self) -> float:
        if self.slot_steps == 0:
            return 0.0
        return self.live_slot_steps / self.slot_steps

    @property
    def padded_decode_frac(self) -> float:
        if self.slot_steps == 0:
            return 0.0
        return 1.0 - self.slot_utilization

    @property
    def prefix_hit_rate(self) -> float:
        if self.prefix_lookup_blocks == 0:
            return 0.0
        return self.prefix_hit_blocks / self.prefix_lookup_blocks

    @property
    def spec_acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens accepted, over live
        row-rounds (each proposes ``spec_gamma``)."""
        if self.spec_live_rounds == 0:
            return 0.0
        return self.spec_accepted / (
            self.spec_live_rounds * max(self.spec_gamma, 1)
        )

    @property
    def spec_tokens_per_round(self) -> float:
        """Committed tokens per live row-round ∈ [1, gamma+1] — the
        decode-throughput multiplier speculation buys."""
        if self.spec_live_rounds == 0:
            return 0.0
        return self.spec_committed / self.spec_live_rounds

    def _stall_pct(self, q: float) -> float:
        if not self.decode_stall_samples:
            return 0.0
        return float(np.percentile(np.asarray(self.decode_stall_samples), q))

    def _queue_wait_pct(self, q: float) -> float:
        if not self.queue_wait_samples:
            return 0.0
        return float(np.percentile(np.asarray(self.queue_wait_samples), q))

    @property
    def queue_wait_p50(self) -> float:
        return self._queue_wait_pct(50.0)

    @property
    def queue_wait_p95(self) -> float:
        return self._queue_wait_pct(95.0)

    @property
    def decode_stall_p50(self) -> float:
        return self._stall_pct(50.0)

    @property
    def decode_stall_p95(self) -> float:
        return self._stall_pct(95.0)

    @property
    def decode_stall_max(self) -> float:
        if not self.decode_stall_samples:
            return 0.0
        return float(max(self.decode_stall_samples))

    def note_harvest(self, tokens: np.ndarray, mask: np.ndarray) -> None:
        """Fold one harvested [B, N] (or [N]) response block into the
        generation canary: per-row generated lengths and the repeated
        adjacent-token fraction. Host numpy on already-fetched arrays."""
        tokens = np.atleast_2d(np.asarray(tokens))
        mask = np.atleast_2d(np.asarray(mask, np.float32))
        lens = mask.sum(axis=1)
        self.gen_len_samples.extend(float(n) for n in lens)
        if tokens.shape[1] > 1:
            pair_mask = mask[:, 1:] * mask[:, :-1]
            self.repeat_pairs += int(
                ((tokens[:, 1:] == tokens[:, :-1]) * pair_mask).sum()
            )
            self.repeat_pairs_total += int(pair_mask.sum())

    @property
    def repetition_frac(self) -> float:
        if self.repeat_pairs_total == 0:
            return 0.0
        return self.repeat_pairs / self.repeat_pairs_total

    def _gen_len_pct(self, q: float) -> float:
        if not self.gen_len_samples:
            return 0.0
        return float(np.percentile(np.asarray(self.gen_len_samples), q))

    def metrics(self) -> Dict[str, float]:
        """The observability-layer gauges (registered in
        ``tests/test_metric_names.py``; see docs/OBSERVABILITY.md)."""
        stats: Dict[str, float] = {}
        stats["throughput/slot_utilization"] = self.slot_utilization
        stats["rollout/padded_decode_frac"] = self.padded_decode_frac
        stats["rollout/refill_prefills"] = float(self.refill_prefills)
        stats["rollout/refilled_rows"] = float(self.refilled_rows)
        stats["rollout/segments"] = float(self.segments)
        stats["engine/queue_wait_s"] = float(self.queue_wait_s)
        # per-request queue-wait percentiles: the admission-control number —
        # the serve SLO check and the trainer share these samples
        stats["engine/queue_wait_p50"] = self.queue_wait_p50
        stats["engine/queue_wait_p95"] = self.queue_wait_p95
        stats["memory/kv_cache_bytes"] = float(self.kv_cache_bytes)
        # decode-stall percentiles (docs/PERFORMANCE.md "Chunked prefill"):
        # how long live decode slots waited on prefill work — the measured
        # number behind the chunked-prefill scheduling claim
        stats["rollout/decode_stall_p50"] = self.decode_stall_p50
        stats["rollout/decode_stall_p95"] = self.decode_stall_p95
        stats["rollout/decode_stall_max"] = self.decode_stall_max
        stats["rollout/prefill_chunks"] = float(self.prefill_chunk_calls)
        # generation canary (observability/health.py): length percentiles
        # and repeated-token fraction over everything harvested so far
        if self.gen_len_samples:
            stats["rollout/gen_len_p50"] = self._gen_len_pct(50.0)
            stats["rollout/gen_len_p95"] = self._gen_len_pct(95.0)
            stats["rollout/repetition_frac"] = self.repetition_frac
        if self.kv_blocks_total:
            stats["engine/kv_blocks_in_use"] = float(self.kv_blocks_in_use)
            stats["engine/block_pool_occupancy"] = self.kv_blocks_in_use / max(
                self.kv_blocks_total, 1
            )
            # which decode/prefill compute the programs ran — an A/B
            # artifact (or a dashboard) can tell kernel from gather runs
            # without config archaeology
            stats["engine/decode_kernel_pallas"] = float(
                self.decode_kernel_pallas
            )
            stats["engine/prefill_kernel_pallas"] = float(
                self.prefill_kernel_pallas
            )
            # the refill gather/scatter tax, measured: 0 under the
            # in-place prefill kernel
            stats["engine/refill_gather_bytes"] = float(
                self.refill_gather_bytes
            )
            stats["engine/refill_scatter_bytes"] = float(
                self.refill_scatter_bytes
            )
        if self.prefix_enabled:
            stats["engine/prefix_hit_rate"] = self.prefix_hit_rate
            stats["engine/prefix_tokens_saved"] = float(self.prefix_tokens_saved)
        if self.preempted_rows:
            stats["engine/preempted_rows"] = float(self.preempted_rows)
        if self.host_tier_enabled:
            # host-tier effectiveness: prompt columns whose KV came back
            # over PCIe instead of through a prefill forward
            stats["engine/host_tier_hit_blocks"] = float(self.host_tier_hit_blocks)
            stats["engine/host_tier_tokens_saved"] = float(
                self.host_tier_tokens_saved
            )
        if self.spec_gamma:
            # speculative decode segments: how much of the draft's work the
            # target kept, and the per-round throughput multiplier
            stats["engine/spec_acceptance_rate"] = self.spec_acceptance_rate
            stats["engine/spec_tokens_per_round"] = self.spec_tokens_per_round
            stats["rollout/spec_rounds"] = float(self.spec_rounds)
            # which verify compute the rounds ran — same contract as the
            # decode/prefill kernel gauges above
            stats["engine/spec_verify_kernel_pallas"] = float(
                self.spec_verify_kernel_pallas
            )
        return stats


class Engine:
    """The minimal contract every generation engine implements: feed
    prompts with per-row RNG chain starts, turn the crank, collect
    individually completed sequences. Trainers talk only to this surface
    (``_collect_continuous``; ``generate`` routes through
    :class:`SerialEngine`), so backends — dense, paged, and eventually the
    disaggregated actor fleet — swap under one interface.
    """

    stats: EngineStats

    def enqueue_prompts(
        self,
        input_ids: np.ndarray,
        attention_mask: np.ndarray,
        keys: np.ndarray,
        metas: Optional[List[Any]] = None,
    ) -> None:
        raise NotImplementedError

    def step(self) -> List[CompletedSequence]:
        raise NotImplementedError

    @property
    def busy(self) -> bool:
        raise NotImplementedError

    def run(self) -> List[CompletedSequence]:
        """Drain queue + slots to completion (small-scale convenience; the
        trainers interleave :meth:`step` with downstream scoring instead)."""
        out: List[CompletedSequence] = []
        while self.busy:
            out.extend(self.step())
        return out


class SerialEngine(Engine):
    """Plain batch generate behind the Engine interface.

    Wraps a jitted ``fn(params, input_ids, attention_mask, rng)`` — the
    trainers' serial rollout program, UNCHANGED (it is the bit-equivalence
    reference). The streaming surface buffers whole chunks with the rng
    they were submitted under, so each :meth:`step` reproduces exactly one
    serial ``generate`` call.
    """

    def __init__(self, generate_fn: Callable, params: Any, pad_token_id: int):
        self._fn = generate_fn
        self.params = params
        self.pad_token_id = int(pad_token_id)
        self._chunks: deque = deque()
        self._submitted = 0
        self.stats = EngineStats()

    def generate(self, input_ids, attention_mask, rng):
        """The batch-synchronous path ``TPUBaseTrainer.generate`` routes
        through — returns whatever the wrapped program returns (a
        GenerationOutput, or ``(output, stats)`` for the speculative
        sampler)."""
        return self._fn(self.params, input_ids, attention_mask, rng)

    def enqueue_prompts(self, input_ids, attention_mask, keys=None, metas=None):
        raise NotImplementedError(
            "SerialEngine decodes whole chunks under one rng: use "
            "submit_chunk(input_ids, attention_mask, rng) (per-row keys "
            "are a continuous-batching concept)"
        )

    def submit_chunk(self, input_ids, attention_mask, rng, metas=None) -> None:
        input_ids = np.asarray(input_ids, np.int32)
        attention_mask = np.asarray(attention_mask, np.int32)
        idx = list(range(self._submitted, self._submitted + input_ids.shape[0]))
        self._submitted += input_ids.shape[0]
        self._chunks.append((idx, input_ids, attention_mask, rng, metas))

    @property
    def busy(self) -> bool:
        return bool(self._chunks)

    def step(self) -> List[CompletedSequence]:
        if not self._chunks:
            return []
        idx, ids, mask, rng, metas = self._chunks.popleft()
        t0 = time.perf_counter()
        out = self.generate(ids, mask, rng)
        if type(out) is tuple:  # speculative sampler: (output, stats)
            out = out[0]
        host = {
            "tokens": np.asarray(out.response_tokens),
            "logprobs": np.asarray(out.response_logprobs),
            "values": np.asarray(out.response_values),
            "mask": np.asarray(out.response_mask),
        }
        self.stats.decode_s += time.perf_counter() - t0
        self.stats.segments += 1
        n = len(idx)
        steps = int(host["mask"].sum(axis=1).max()) if n else 0
        self.stats.decode_steps += steps
        self.stats.slot_steps += steps * n
        self.stats.live_slot_steps += int(host["mask"].sum())
        self.stats.harvested += n
        self.stats.note_harvest(host["tokens"], host["mask"])
        return [
            CompletedSequence(
                index=idx[i],
                prompt_ids=ids[i],
                prompt_mask=mask[i],
                tokens=host["tokens"][i],
                logprobs=host["logprobs"][i],
                values=host["values"][i],
                mask=host["mask"][i],
                meta=metas[i] if metas is not None else None,
            )
            for i in range(n)
        ]


class ContinuousEngine(Engine):
    """Slot-refill decode over a fixed ``[B]`` slot batch.

    ``fns`` are the compiled programs from
    :func:`trlx_tpu.ops.slot_refill.make_slot_refill_fns` — their
    ``paged`` field selects the KV backend; ``span`` is an optional
    ``Observability.span``-shaped callable — each segment runs under a
    fenced ``rollout/segment`` span so the trace shows device-true decode
    time per segment. ``tracer`` (an ``Observability.tracer``) additionally
    emits per-request lifecycle spans at harvest — ``engine/queue_wait`` →
    ``engine/prefill`` → ``engine/decode`` on a per-slot track — so a stall
    is attributable to one row, not smeared over the batch. ``prefix_cache``
    (paged backend only) turns on shared-prefix prefill skipping.

    ``prefill_chunk`` (paged backend only, ``engine.prefill_chunk``) turns
    on chunked-prefill *scheduling*: admitted prompts prefill one
    fixed-size span per :meth:`step`, interleaved with decode segments, so
    a long prompt can never stall live decode slots longer than one
    chunk's prefill (the stall mode PipelineRL, arXiv:2509.19128,
    identifies for long-sequence RL generation; the
    ``rollout/decode_stall_*`` gauges measure it). Spans align to absolute
    multiples of the chunk size, mid-prompt spans run cache-only chunk
    programs, the final span is the ordinary refill program (hit = its
    start) — harvested sequences stay bit-identical to the monolithic
    path across chunk sizes (``tests/test_paged_attention.py``,
    ``tests/test_engine.py``). Each per-request chunk additionally lands
    as an ``engine/prefill_chunk`` span on the slot's trace track.

    Speculative decode segments (``fns.speculative = k > 0``, paged
    backend): each segment runs draft-propose → paged-verify → accept
    ROUNDS instead of single-token steps, committing 1..k+1 tokens per
    live row per round — ``params`` is then a ``(target, draft)`` tuple
    (swapped atomically by :meth:`swap_params`), harvested rows stay
    bit-identical to solo ``ops/speculative.py`` runs per row
    (``tests/test_spec_engine.py``), and the ``engine/spec_*`` gauges
    report acceptance. Admission, chunked prefill, prefix-cache hits and
    insertion are UNCHANGED — speculation only replaces the decode
    segment's inner loop.
    """

    def __init__(
        self,
        fns: Any,  # SlotRefillFns
        params: Any,
        pad_token_id: int,
        span: Optional[Callable[..., Any]] = None,
        tracer: Any = None,
        prewarm: bool = True,
        prefix_cache: bool = False,
        prefix_capacity_blocks: int = 0,
        prefill_chunk: int = 0,
    ):
        import jax.numpy as jnp  # deferred: host module, device state here only

        self._jnp = jnp
        self.fns = fns
        self.params = params
        self.pad_token_id = int(pad_token_id)
        self._span = span
        self._tracer = tracer
        self.state = fns.init_state()
        self.B = fns.batch_size
        self.P = fns.prompt_len
        self.N = fns.max_new_tokens
        self._queue: deque = deque()
        self._slots: List[Optional[_Request]] = [None] * self.B
        # True once the slot's FINAL prefill span ran (the refill program
        # scattered its SlotState row: logits seeded, done=False). Chunked
        # prefill leaves a slot unseeded — and hence outside harvest and
        # decode-block growth — until its last span lands.
        self._seeded: List[bool] = [False] * self.B
        self._submitted = 0
        self.stats = EngineStats()
        self._chunk = int(prefill_chunk)
        if self._chunk < 0:
            raise ValueError(f"prefill_chunk {self._chunk} must be >= 0")
        # serving extensions (all default-off; single-threaded like the
        # rest of the engine — the serve pump thread owns them):
        # host-RAM tier of evicted prefix blocks (attach_host_tier)
        self.host_tier: Any = None
        # slots only interactive-class requests may take, so a saturating
        # batch workload cannot push interactive TTFT past one admission
        self.reserve_slots = 0
        # requests that failed admission-side (tenant quota): the owner
        # drains these after step() — trainer traffic never lands here
        self.failed: deque = deque()

        self.spec = getattr(fns, "paged", None)
        # speculative decode segments (ops/slot_refill.py speculative=k):
        # params become a (target, draft) tuple, buffers widen to
        # N + gamma + 1, caches to S = P + N + gamma, and rows advance
        # VARIABLE amounts per round — the per-slot step counters below
        # track the true committed lengths instead of a uniform bound
        self._gamma = int(getattr(fns, "speculative", 0) or 0)
        self._S = self.P + self.N + self._gamma
        self.stats.spec_gamma = self._gamma
        # device spec counters are cumulative over the fns-state lifetime;
        # per-collection stats are deltas against this snapshot
        self._spec_base = {
            "rounds": 0, "accepted": 0, "live_rounds": 0, "committed": 0
        }
        self.allocator: Optional[BlockAllocator] = None
        self.prefix: Optional[PrefixCache] = None
        if self.spec is not None:
            S = self._S
            self._bs = self.spec.block_size
            self._TB = num_table_blocks(S, self._bs)
            self.allocator = BlockAllocator(self.spec.max_blocks)
            if prefix_cache:
                cache_layout.refuse(self.state.cache, "prefix_cache", self._bs)
                self.prefix = PrefixCache(self._bs, prefix_capacity_blocks)
                self.stats.prefix_enabled = True
            # host mirror of the device block table — authoritative between
            # programs (refill programs apply the same rows on device;
            # segment-growth pushes the whole mirror)
            self._tables = np.zeros((self.B, self._TB), np.int32)
            self._row_blocks: List[Optional[List[int]]] = [None] * self.B
            # leading table entries with real (allocated) backing per slot
            self._alloc_upto = [0] * self.B
            # upper bound on each slot's decode step (segments survived)
            self._steps_bound = [0] * self.B
            self.stats.kv_blocks_total = self.spec.max_blocks - 1
            # gauges name the compute the slot-refill programs were built
            # with; a selected kernel runs (interpreted off-TPU) or raises
            self.stats.decode_kernel_pallas = (
                getattr(fns, "decode_kernel", "xla") == "pallas"
            )
            self.stats.prefill_kernel_pallas = (
                getattr(fns, "prefill_kernel", "xla") == "pallas"
            )
            self.stats.spec_verify_kernel_pallas = bool(
                self._gamma and self.stats.decode_kernel_pallas
            )
            self._block_bytes = block_bytes(self.state.cache)
            # per-cache-column bytes (all layers, k+v): the unit of the
            # analytic refill gather/scatter accounting
            self._col_bytes = self._block_bytes / max(self._bs, 1)
        elif prefix_cache:
            raise ValueError(
                "engine.prefix_cache requires the paged KV backend "
                "(engine.backend: paged) — dense per-slot caches cannot "
                "share blocks"
            )
        elif self._chunk:
            raise ValueError(
                "engine.prefill_chunk requires the paged KV backend "
                "(engine.backend: paged) — the chunk programs commit "
                "prompt spans through the block table"
            )
        self.stats.kv_cache_bytes = kv_bytes(self.state.cache)
        if self._gamma:
            # the draft's dense [B, S] cache is persistent engine state too
            self.stats.kv_cache_bytes += kv_bytes(self.state.d_cache)
        # identity of the params the pool's committed KV (and hence every
        # prefix-cache entry) was computed under — a different params tree
        # invalidates all cached KV (begin_collection flushes)
        self._kv_params = params
        # memoized version counter for the weight-sync path: per-segment
        # swap checks compare one int instead of adopting/flushing on every
        # fresh params object (each publish is a new copy, so the identity
        # test alone would false-negative and flush a still-valid cache)
        self._params_version: Optional[int] = None
        if prewarm:
            # once per SlotRefillFns (the fns — and their compiled bucket
            # programs — outlive this engine via the trainer's program
            # cache; later engines skip straight through)
            self.state = self.fns.prewarm(self.params, self.state)

    def begin_collection(self, params: Any, version: Optional[int] = None) -> None:
        """Reuse this engine for a fresh collection: reset the
        per-collection stats, adopt the (possibly updated) policy params,
        and drop any leftovers of an aborted run. Cached prefix KV is
        valid ONLY under the params it was computed with — a new params
        tree (the policy trained in between) flushes the prefix cache;
        identical params (repeated eval, back-to-back collections without
        an update) keep it warm, which is where cross-collection prefill
        savings come from. ``version`` (the weight-sync path) memoizes a
        cheap counter: a matching version skips the flush even when the
        params object is a fresh copy of the same weights."""
        self._queue.clear()
        self.failed.clear()
        for slot in range(self.B):
            if self._slots[slot] is None:
                continue
            # aborted-collection leftovers: free the slot (and its blocks —
            # a refill that died inside _prepare_row assigned the slot but
            # never wrote its block list, hence the None guard)
            if self.spec is not None:
                if self._row_blocks[slot] is not None:
                    self.allocator.release(self._row_blocks[slot])
                self._row_blocks[slot] = None
                self._alloc_upto[slot] = 0
                self._steps_bound[slot] = 0
            self._slots[slot] = None
            self._seeded[slot] = False
        if not bool(np.asarray(self.state.done).all()):
            # freeze any still-decoding device rows from the aborted run
            self.state = self.state._replace(
                done=self._jnp.ones((self.B,), bool)
            )
        self._adopt_params(params, version)
        self.stats = EngineStats(
            kv_cache_bytes=self.stats.kv_cache_bytes,
            prefix_enabled=self.stats.prefix_enabled,
            host_tier_enabled=self.stats.host_tier_enabled,
            kv_blocks_total=self.stats.kv_blocks_total,
            decode_kernel_pallas=self.stats.decode_kernel_pallas,
            prefill_kernel_pallas=self.stats.prefill_kernel_pallas,
            spec_verify_kernel_pallas=self.stats.spec_verify_kernel_pallas,
            spec_gamma=self._gamma,
        )
        if self._gamma:
            self._spec_base = self._read_spec_counters()
        if self.allocator is not None:
            # per-collection high-water, not lifetime
            self.allocator.high_water = self.allocator.blocks_in_use

    @staticmethod
    def _same_params(a: Any, b: Any) -> bool:
        """Identity, element-wise over (target, draft) params tuples — the
        speculative engine's params often arrive as a freshly-built 2-tuple
        around the SAME trees every call, and the naked identity test would
        false-negative and flush a still-valid prefix cache."""
        if type(a) is tuple and type(b) is tuple and len(a) == len(b):
            return all(x is y for x, y in zip(a, b))
        return a is b

    def _params_changed(self, params: Any, version: Optional[int]) -> bool:
        """One int compare on the versioned weight-sync path, identity on
        the unversioned path — never a tree walk. The spec engine's
        (target, draft) tuple swaps ATOMICALLY: both trees arrive in one
        params object adopted at one segment boundary."""
        if version is not None and self._params_version is not None:
            return version != self._params_version
        return not self._same_params(params, self._kv_params)

    def _adopt_params(self, params: Any, version: Optional[int]) -> None:
        if self._params_changed(params, version):
            if self.prefix is not None:
                self.prefix.clear(self.allocator)
            if self.host_tier is not None:
                # spilled KV is valid only under the params that computed
                # it — exactly like the device-side entries just cleared
                self.host_tier.clear()
            self._kv_params = params
        self._params_version = version
        self.params = params

    def attach_host_tier(self, tier: Any) -> None:
        """Wire a :class:`~trlx_tpu.serve.tiering.HostTier` behind the
        prefix cache: evicted entries spill their block KV host-side, and
        admission re-lands host-resident chunks instead of re-prefilling.
        The tier is owned by this engine's (single) driving thread."""
        if self.prefix is None:
            raise ValueError(
                "host tiering requires the prefix cache "
                "(engine.prefix_cache: true) — only committed prefix "
                "entries ever spill"
            )
        self.host_tier = tier
        self.stats.host_tier_enabled = True
        self.prefix.spill = self._spill_entry

    def _spill_entry(self, entry: Any) -> None:
        """Prefix-cache eviction hook: copy the victim's block rows to the
        host pool before the cache drops its ref (committed KV is
        immutable, so the copy is valid even while a live row shares the
        block)."""
        self.host_tier.spill(entry.digest, self.state.cache.pool, entry.block)

    def swap_params(self, params: Any, version: Optional[int] = None) -> bool:
        """In-flight weight sync (docs/ASYNC_RL.md): adopt updated params
        MID-COLLECTION at a segment boundary. Live rows keep their KV (the
        sequence becomes a bounded param-version mixture — the behavior
        logprobs the sampler records stay exact), but cached *shared*
        prefix KV under the old params must never seed a future row's
        prefill: a changed version flushes the prefix cache, exactly like
        ``begin_collection``. Returns True when the params actually
        changed; a matching memoized version is a cheap no-op. With
        chunked prefill, a swap between a row's chunks makes its *prompt*
        KV a bounded param-version mixture too — same contract as live
        decode rows: the sampler's recorded behavior logprobs stay exact,
        the mixture is what actually generated the sequence."""
        if not self._params_changed(params, version):
            self._params_version = version if version is not None else self._params_version
            return False
        self._adopt_params(params, version)
        return True

    # -- feeding ---------------------------------------------------------

    def enqueue_prompts(
        self,
        input_ids: np.ndarray,  # [b, p] left-padded, p <= P
        attention_mask: np.ndarray,  # [b, p]
        keys: np.ndarray,  # [b, 2] per-row RNG chain starts
        metas: Optional[List[Any]] = None,
        tenant: Optional[str] = None,
        klass: str = "actor",
    ) -> None:
        """Queue a prompt batch. Rows narrower than the engine width are
        left-padded to ``P`` (bit-stream-neutral only when the caller also
        runs its reference ``generate`` at width ``P``); wider rows are an
        error — the KV cache was sized for ``P``. ``tenant`` scopes the
        batch's prefix-cache namespace and block quota; ``klass`` is its
        priority class (:data:`SERVE_CLASSES`) — the trainer's default
        ``actor`` keeps the pre-serving FIFO behavior when nothing of a
        better class is queued."""
        if klass not in _CLASS_RANK:
            raise ValueError(
                f"unknown priority class {klass!r}: expected one of "
                f"{SERVE_CLASSES}"
            )
        input_ids = np.asarray(input_ids, np.int32)
        attention_mask = np.asarray(attention_mask, np.int32)
        b, p = input_ids.shape
        if p > self.P:
            raise ValueError(
                f"prompt width {p} exceeds the engine's padded width {self.P}; "
                "size the engine from the widest prompt chunk (or pin the "
                "prompt loader's width with fixed_length)"
            )
        if p < self.P:
            pad = self.P - p
            input_ids = np.concatenate(
                [np.full((b, pad), self.pad_token_id, np.int32), input_ids], axis=1
            )
            attention_mask = np.concatenate(
                [np.zeros((b, pad), np.int32), attention_mask], axis=1
            )
        keys = np.asarray(keys)
        t_enqueue = time.perf_counter()
        for i in range(b):
            self._queue.append(
                _Request(
                    index=self._submitted,
                    input_ids=input_ids[i],
                    attention_mask=attention_mask[i],
                    key=keys[i],
                    meta=metas[i] if metas is not None else None,
                    t_enqueue=t_enqueue,
                    tenant=tenant,
                    klass=klass,
                )
            )
            self._submitted += 1

    # -- state -----------------------------------------------------------

    @property
    def pending(self) -> int:
        """Prompts queued but not yet in a slot."""
        return len(self._queue)

    @property
    def live(self) -> int:
        """Slots currently holding an unharvested sequence."""
        return sum(1 for r in self._slots if r is not None)

    @property
    def busy(self) -> bool:
        return self.live > 0 or self.pending > 0

    # -- paged-block bookkeeping ----------------------------------------

    def _alloc_blocks(self, n: int, tenant: Optional[str] = None) -> List[int]:  # acquires: kv-block-ref
        """Allocate with one eviction retry: on pool pressure, drop LRU
        prefix-cache entries (their blocks free unless a live row still
        shares them) before giving up. A quota'd tenant's pressure evicts
        ONLY that tenant's entries — another tenant's working set is never
        shed to admit this one (docs/SERVING.md)."""
        if n == 0:
            return []
        try:
            return self.allocator.alloc(n, tenant=tenant)
        except TenantQuotaExceeded:
            if self.prefix is None:
                raise
            quota = self.allocator.tenant_quota(tenant)
            headroom = max(
                (quota or 0) - self.allocator.tenant_blocks_in_use(tenant), 0
            )
            self.stats.prefix_evicted_blocks += self.prefix.evict(
                self.allocator, blocks_needed=n - headroom, tenant=tenant
            )
            # still over quota → the caller fails THIS request, not the engine
            return self.allocator.alloc(n, tenant=tenant)
        except BlockPoolExhausted:
            if self.prefix is not None:
                self.stats.prefix_evicted_blocks += self.prefix.evict(
                    self.allocator, blocks_needed=n - self.allocator.blocks_free
                )
                return self.allocator.alloc(n, tenant=tenant)  # exhausted again → caller's error
            raise

    def _note_block_usage(self) -> None:
        self.stats.kv_blocks_in_use = self.allocator.high_water
        self.stats.kv_bytes_high_water = (
            self.allocator.high_water * self._block_bytes
        )

    def _prepare_row(self, req: "_Request", slot: int) -> int:  # acquires: row-block-ref(object)
        """Assign blocks for one refilled row: shared prefix blocks from
        the cache (refcount++), host-tier re-lands for chunks beyond the
        device hit (spilled KV written back verbatim — bit-identical to a
        cold prefill by construction), fresh private blocks for the rest
        of the prompt region. Returns the row's hit length in cache
        columns (block-aligned, capped so at least one prompt column is
        always recomputed — the refill forward must produce last-position
        logits to seed the sampler)."""
        shared: List[int] = []
        cap = (self.P - 1) // self._bs
        if self.prefix is not None:
            shared = self.prefix.match(
                req.input_ids, req.attention_mask, tenant=req.tenant
            )
            shared = shared[:cap]
            # denominator = blocks a hit could ever cover — the cap above
            # always recomputes the last prompt block, so a fully warm
            # repeat prompt reaches hit_rate 1.0
            self.stats.prefix_lookup_blocks += cap
            self.stats.prefix_hit_blocks += len(shared)
        # retain the matched chain BEFORE allocating: _alloc_blocks may
        # evict prefix-cache entries under pool pressure, and a cache-only
        # ref on a just-matched block would let eviction free it and hand
        # it back as this row's writable "fresh" block (aliasing a shared
        # prefix position with a write target). With the row's ref held,
        # eviction only ever drops the cache's ref — the block survives.
        self.allocator.retain(shared)  # no-op for a cold miss (empty hit)
        relanded = self._reland_from_tier(req, len(shared), cap, shared)
        hit_chain = shared + relanded
        hit = len(hit_chain) * self._bs
        n_prompt_blocks = (self.P - 1) // self._bs + 1
        try:
            fresh = self._alloc_blocks(
                n_prompt_blocks - len(hit_chain), tenant=req.tenant
            )
        except (BlockPoolExhausted, TenantQuotaExceeded):
            self.allocator.release(hit_chain)  # no leak on the error path
            raise
        row = np.zeros(self._TB, np.int32)
        row[: len(hit_chain)] = hit_chain
        row[len(hit_chain) : n_prompt_blocks] = fresh
        self._tables[slot] = row
        self._row_blocks[slot] = hit_chain + fresh
        self._alloc_upto[slot] = n_prompt_blocks
        self._steps_bound[slot] = 0
        return hit

    def _reland_from_tier(
        self, req: "_Request", n_hit: int, cap: int, shared: List[int]
    ) -> List[int]:  # acquires: kv-block-ref
        """Probe the host tier for the consecutive chunks beyond the
        device hit; write each host-resident chunk's spilled KV into a
        fresh device block and commit it back into the tenant's radix
        chain (so siblings share it and the cache owns a ref, exactly like
        a prefilled block). Returns the re-landed blocks, row ref held."""
        if self.host_tier is None or self.prefix is None or n_hit >= cap:
            return []
        digests = self.prefix.chain_digests(
            req.input_ids, req.attention_mask, cap, tenant=req.tenant
        )
        run: List[bytes] = []
        for i in range(n_hit, min(cap, len(digests))):
            if not self.host_tier.probe(digests[i]):
                break
            run.append(digests[i])
        if not run:
            return []
        try:
            blocks = self._alloc_blocks(len(run), tenant=req.tenant)
        except (BlockPoolExhausted, TenantQuotaExceeded):
            return []  # the tier is an optimization: fall back to re-prefill
        pool = self.host_tier.reland_many(run, self.state.cache.pool, blocks)
        self.state = self.state._replace(
            cache=self.state.cache._replace(pool=pool)
        )
        self.prefix.insert(
            req.input_ids,
            req.attention_mask,
            shared + blocks,
            self.allocator,
            tenant=req.tenant,
        )
        self.stats.host_tier_hit_blocks += len(blocks)
        self.stats.host_tier_tokens_saved += len(blocks) * self._bs
        return blocks

    def _ensure_decode_blocks(self, segment_len: int) -> bool:
        """Grow each live row's table to cover the columns the next decode
        segment may write — lazy allocation is what makes the pool's
        high-water track live tokens. Returns True when any table changed
        (the mirror must be pushed to device)."""
        dirty = False
        for slot in range(self.B):
            if self._slots[slot] is None or not self._seeded[slot]:
                # still-prefilling slots decode nothing this segment — their
                # prompt blocks were assigned at admission, decode blocks
                # wait until the final span seeds them
                continue
            # a spec segment commits up to (gamma+1) tokens per round per
            # live row, bounded by the row hitting N
            per_seg = segment_len * (self._gamma + 1) if self._gamma else segment_len
            need_cols = self.P + min(
                self.N, self._steps_bound[slot] + per_seg
            )
            need_blocks = (need_cols - 1) // self._bs + 1
            have = self._alloc_upto[slot]
            if need_blocks > have:
                fresh = self._alloc_blocks(need_blocks - have)
                self._tables[slot, have:need_blocks] = fresh
                self._row_blocks[slot].extend(fresh)
                self._alloc_upto[slot] = need_blocks
                dirty = True
        return dirty

    def _push_tables(self) -> None:
        self.state = self.state._replace(
            cache=self.state.cache._replace(
                block_table=self._jnp.asarray(self._tables)
            )
        )

    # -- the slot-refill state machine -----------------------------------

    def _read_spec_counters(self) -> Dict[str, int]:
        """Fetch the device-cumulative spec counters (tiny scalars; the
        caller already blocked on the segment they were produced by)."""
        return {
            k: int(np.asarray(getattr(self.state, k)))
            for k in ("rounds", "accepted", "live_rounds", "committed")
        }

    def _decoding(self) -> int:
        """Slots holding a seeded (decoding or awaiting-harvest) sequence —
        the population a prefill event stalls."""
        return sum(
            1
            for s in range(self.B)
            if self._slots[s] is not None and self._seeded[s]
        )

    def _note_prefill_event(self, waiting: int, t0: float, t1: float) -> None:
        """Decode-stall accounting: one sample per prefill event that ran
        while ``waiting`` seeded slots sat idle (docs/PERFORMANCE.md
        "Chunked prefill") — under chunked scheduling no sample can exceed
        one chunk's prefill, which is the whole point."""
        self.stats.refill_s += t1 - t0
        if waiting > 0:
            self.stats.decode_stall_s += t1 - t0
            self.stats.decode_stall_samples.append(t1 - t0)

    def _note_refill_io(self, rows: int, gather_cols: int, span_cols: int) -> None:
        """Analytic bytes of the transient dense view a gather-flavor
        prefill program moves (pool → view on entry, written span → pool on
        exit). The in-place prefill kernel moves none — the measured 0 the
        ENGINE_PREFILL A/B commits."""
        if self.stats.prefill_kernel_pallas:
            return
        self.stats.refill_gather_bytes += int(rows * gather_cols * self._col_bytes)
        self.stats.refill_scatter_bytes += int(rows * span_cols * self._col_bytes)

    def _rank(self, req: "_Request") -> int:
        return _CLASS_RANK.get(req.klass, _DEFAULT_RANK)

    def _pop_next(self, only_interactive: bool = False) -> Optional["_Request"]:
        """Best-class-first, FIFO-within-class (by submission index) pop —
        a requeued preemption victim's lower index restores its original
        place in its class. With ``only_interactive`` (the reserve-slot
        guard) only rank-0 requests are eligible."""
        best_i = -1
        best_key = None
        for i, req in enumerate(self._queue):
            rank = self._rank(req)
            if only_interactive and rank > 0:
                continue
            key = (rank, req.index)
            if best_key is None or key < best_key:
                best_i, best_key = i, key
        if best_key is None:
            return None
        req = self._queue[best_i]
        del self._queue[best_i]
        return req

    def _preempt_slot(self, slot: int) -> None:  # releases: row-block-ref(object)
        """Vacate one still-prefilling slot: committed prompt chunks are
        inserted into the tenant's radix chain FIRST (insert retains the
        blocks, so the committed work survives the row's release and
        re-lands as a prefix hit on re-admission), then the row's block
        refs drop and the request returns to the queue."""
        req = self._slots[slot]
        pos = req.prefill_pos or 0
        if self.prefix is not None and pos >= self._bs:
            n_committed = min(pos // self._bs, (self.P - 1) // self._bs)
            self.prefix.insert(
                req.input_ids,
                req.attention_mask,
                list(self._tables[slot, :n_committed]),
                self.allocator,
                tenant=req.tenant,
            )
        self.allocator.release(self._row_blocks[slot])
        self._row_blocks[slot] = None
        self._alloc_upto[slot] = 0
        self._steps_bound[slot] = 0
        self._slots[slot] = None
        self._seeded[slot] = False
        req.prefill_pos = None
        self._queue.append(req)
        self.stats.preempted_rows += 1

    def _preempt_for_priority(self) -> None:
        """The preemption seam (docs/SERVING.md): queued higher-class
        requests that cannot find a free slot vacate still-prefilling
        lower-class slots at the step boundary. Seeded (decoding) slots
        are never preempted — their KV would be lost mid-sequence; the
        chunked-prefill scheduler makes prefilling slots cheap to vacate
        (at most one chunk of uncommitted work)."""
        if self.spec is None or not self._queue:
            return
        free = sum(1 for s in range(self.B) if self._slots[s] is None)
        waiting = sorted(self._rank(r) for r in self._queue)
        # worst class first, least-progressed first: lose the least work
        victims = sorted(
            (
                s
                for s in range(self.B)
                if self._slots[s] is not None and not self._seeded[s]
            ),
            key=lambda s: (
                -self._rank(self._slots[s]),
                self._slots[s].prefill_pos or 0,
            ),
        )
        for slot in victims:
            vrank = self._rank(self._slots[slot])
            demand = sum(1 for r in waiting if r < vrank)
            if demand <= free:
                continue  # free slots already cover the outranking demand
            waiting.append(vrank)
            self._preempt_slot(slot)
            free += 1

    def _admit(self) -> None:
        """Move queued prompts into free slots, best priority class first
        (FIFO within a class). Dense backend: the whole prompt prefills
        immediately (one grouped gather-prefill-scatter). Paged backend:
        blocks are assigned (prefix hits → shared, host-tier re-lands,
        rest fresh) and the row's ``prefill_pos`` starts at its hit; the
        actual prefill work runs in :meth:`_advance_prefill` — one span
        per step, so with ``prefill_chunk`` set a long prompt is admitted
        instantly but prefilled incrementally between decode segments.
        ``reserve_slots`` holds the last free slots for interactive-class
        traffic; a tenant whose quota cannot cover its prompt fails onto
        :attr:`failed` instead of failing the engine."""
        self._preempt_for_priority()
        free = deque(s for s in range(self.B) if self._slots[s] is None)
        if not free or not self._queue:
            return
        rows: List[_Request] = []
        slots: List[int] = []
        while free and self._queue:
            if self.reserve_slots > 0:
                non_interactive = sum(
                    1
                    for s in range(self.B)
                    if self._slots[s] is not None
                    and self._rank(self._slots[s]) > 0
                )
                only_interactive = (
                    non_interactive >= self.B - self.reserve_slots
                )
            else:
                only_interactive = False
            req = self._pop_next(only_interactive)
            if req is None:
                break
            slot = free.popleft()
            self._slots[slot] = req
            self._seeded[slot] = False
            rows.append(req)
            slots.append(slot)
        if not rows:
            return
        if self.spec is None:
            waiting = self._decoding()
            t0 = time.perf_counter()
            # gather-prefill-scatter: only the fresh rows run the prefill
            # (bucketed to a power of two inside refill_rows)
            self.state = self.fns.refill_rows(
                self.params,
                self.state,
                np.stack([r.input_ids for r in rows]),
                np.stack([r.attention_mask for r in rows]),
                np.asarray(slots, np.int32),
                np.stack([r.key for r in rows]),
            )
            t1 = time.perf_counter()
            self.stats.refill_prefills += 1
            self.stats.prefill_tokens += self.P * len(rows)
            self._note_prefill_event(waiting, t0, t1)
            for req, slot in zip(rows, slots):
                req.t_refill0 = t0
                req.t_refill1 = t1
                self._seeded[slot] = True
                self.stats.queue_wait_s += max(t0 - req.t_enqueue, 0.0)
                self.stats.queue_wait_samples.append(
                    max(t0 - req.t_enqueue, 0.0)
                )
            self.stats.refilled_rows += len(rows)
            return
        admitted = 0
        for req, slot in zip(rows, slots):
            try:
                hit = self._prepare_row(req, slot)
            except TenantQuotaExceeded as e:
                # the tenant's budget cannot cover this prompt even after
                # shedding its own prefix entries: fail THE REQUEST (the
                # serve frontend turns this into an error response), never
                # the engine — trainer traffic is unquoted and cannot land
                # here
                self._slots[slot] = None
                self._seeded[slot] = False
                self.failed.append((req, str(e)))
                continue
            admitted += 1
            pos0 = hit
            if self._chunk:
                # skip all-masked leading pad columns: they are never
                # attention-visible (slot mask 0 → exact-0.0 softmax
                # terms), so committing their K/V is pure waste — start
                # chunking at the chunk-grid point at or below the first
                # real column (the final span must stay non-empty, hence
                # the (P-1) clamp for degenerate all-pad rows)
                first_real = self.P - int(np.sum(req.attention_mask))
                pos0 = max(
                    hit,
                    min(
                        (first_real // self._chunk) * self._chunk,
                        ((self.P - 1) // self._chunk) * self._chunk,
                    ),
                )
            req.prefill_pos = pos0
            self.stats.prefix_tokens_saved += hit
        self.stats.refilled_rows += admitted
        self._note_block_usage()

    def _next_span(self, pos: int) -> int:
        """End column of the prefill span starting at ``pos``: the whole
        remaining prompt when chunking is off, else up to the next
        ABSOLUTE multiple of the chunk size — prompts admitted at
        different prefix-hit offsets converge onto one span grid after
        their first chunk, so sibling rows group into one program and the
        compiled-span variety stays bounded."""
        if not self._chunk:
            return self.P
        return min(self.P, (pos // self._chunk + 1) * self._chunk)

    def _advance_prefill(self) -> None:
        """Run ONE prefill span for every still-prefilling slot, grouped by
        identical (start, end): mid-prompt spans run the cache-only chunk
        program; a span reaching ``P`` runs the ordinary refill program
        with ``hit = start`` (columns below it are committed — by prefix
        hits, earlier chunks, or both) and seeds the slot for decode.
        Prefix-cache insertion stays strictly AFTER the program calls of
        the event, exactly like the monolithic refill."""
        pending = [
            (s, self._slots[s].prefill_pos)
            for s in range(self.B)
            if self._slots[s] is not None
            and self._slots[s].prefill_pos is not None
        ]
        if not pending:
            return
        waiting = self._decoding()
        by_span: Dict[tuple, List[int]] = {}
        for slot, pos in pending:
            by_span.setdefault((pos, self._next_span(pos)), []).append(slot)
        finished: List[int] = []
        for (start, end), slots in sorted(by_span.items()):
            rows = [self._slots[s] for s in slots]
            t0 = time.perf_counter()
            if end < self.P:
                self.state = self.fns.prefill_chunk_rows(
                    self.params,
                    self.state,
                    np.stack([r.input_ids for r in rows]),
                    np.stack([r.attention_mask for r in rows]),
                    np.stack([self._tables[s] for s in slots]),
                    start=start,
                    end=end,
                )
                self.stats.prefill_chunk_calls += 1
                # the chunk program's gather (start > 0) covers the full
                # S-wide view — key width matches the monolithic pass for
                # bit-parity (ops/slot_refill.py chunk-program docstring)
                self._note_refill_io(
                    len(rows),
                    self._S if start > 0 else 0,
                    end - start,
                )
            else:
                self.state = self.fns.refill_rows(
                    self.params,
                    self.state,
                    np.stack([r.input_ids for r in rows]),
                    np.stack([r.attention_mask for r in rows]),
                    np.asarray(slots, np.int32),
                    np.stack([r.key for r in rows]),
                    table_rows=np.stack([self._tables[s] for s in slots]),
                    hit=start,
                )
                self._note_refill_io(
                    len(rows),
                    self._S if start > 0 else 0,
                    self.P - start,
                )
                finished.extend(slots)
            t1 = time.perf_counter()
            self.stats.refill_prefills += 1
            self.stats.prefill_tokens += (end - start) * len(rows)
            self._note_prefill_event(waiting, t0, t1)
            for req, slot in zip(rows, slots):
                if req.t_refill0 == 0.0:
                    req.t_refill0 = t0
                    self.stats.queue_wait_s += max(t0 - req.t_enqueue, 0.0)
                    self.stats.queue_wait_samples.append(
                        max(t0 - req.t_enqueue, 0.0)
                    )
                if self._tracer is not None and end < self.P:
                    self._tracer.add_complete_event(
                        "engine/prefill_chunk", t0, t1,
                        track=f"engine/slot{slot}", index=req.index,
                        start=start, end=end,
                    )
                if end < self.P:
                    req.prefill_pos = end
                else:
                    req.prefill_pos = None
                    req.t_refill1 = t1
                    self._seeded[slot] = True
        if self.prefix is not None and finished:
            # commit only blocks a later match could USE: _prepare_row caps
            # hits at (P-1)//bs (the last prompt block is always
            # recomputed), so when P is block-aligned the P//bs-th entry
            # would be permanently pinned yet never shareable
            n_full = (self.P - 1) // self._bs
            for slot in finished:
                req = self._slots[slot]
                self.prefix.insert(
                    req.input_ids,
                    req.attention_mask,
                    list(self._tables[slot, :n_full]),
                    self.allocator,
                    tenant=req.tenant,
                )
        self._note_block_usage()

    def _harvest(self) -> List[CompletedSequence]:  # releases: row-block-ref(object)
        done = np.asarray(self.state.done)
        finished = [
            s
            for s in range(self.B)
            # unseeded (still-prefilling) slots read device done=True from
            # their empty SlotState row — they are not finished, they have
            # not started
            if self._slots[s] is not None and self._seeded[s] and done[s]
        ]
        if not finished:
            return []
        idx = self._jnp.asarray(np.asarray(finished, np.int32))
        rows = {
            # spec buffers are [B, N + gamma + 1] (block writes never
            # clip); the caller-visible response is always [N]
            name: getattr(self.state, name)[idx, : self.N]
            for name in ("tokens", "logprobs", "values", "mask")
        }
        # ship immediately: start the device→host copies without blocking —
        # by the time the consumer reads them they have usually landed
        for leaf in rows.values():
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()
        host = {k: np.asarray(v) for k, v in rows.items()}
        self.stats.note_harvest(host["tokens"], host["mask"])
        t_harvest = time.perf_counter()
        completed = []
        for j, slot in enumerate(finished):  # slot order: deterministic
            req = self._slots[slot]
            self._slots[slot] = None
            self._seeded[slot] = False
            self._trace_request(
                req, slot, t_harvest, gen_len=float(host["mask"][j].sum())
            )
            if self.spec is not None:
                # free the row's block refs; blocks the prefix cache (or a
                # sharing sibling) still holds stay allocated. The device
                # table row goes stale, which is harmless: the slot is
                # frozen done and every stale position is slot-masked out
                # of (row-independent) attention until the next refill
                # overwrites the row.
                self.allocator.release(self._row_blocks[slot])
                self._row_blocks[slot] = None
                self._alloc_upto[slot] = 0
                self._steps_bound[slot] = 0
            completed.append(
                CompletedSequence(
                    index=req.index,
                    prompt_ids=req.input_ids,
                    prompt_mask=req.attention_mask,
                    tokens=host["tokens"][j],
                    logprobs=host["logprobs"][j],
                    values=host["values"][j],
                    mask=host["mask"][j],
                    meta=req.meta,
                    t_enqueue=req.t_enqueue,
                    t_prefill0=req.t_refill0,
                    t_prefill1=req.t_refill1,
                    t_harvest=t_harvest,
                )
            )
        self.stats.harvested += len(completed)
        return completed

    def progress_snapshot(self) -> List[tuple]:
        """Per-slot decode progress for token streaming (paged backend):
        ``(index, meta, tokens)`` for every seeded live slot, where
        ``tokens`` is the host copy of the row's committed response so far
        (``_steps_bound`` is exact for live rows — non-spec rows advance
        in lockstep, spec rows read the device step counter; a row that
        finished mid-segment was harvested by the same :meth:`step`, so it
        never appears here with trailing post-eos positions). The serve
        pump diffs consecutive snapshots into stream deltas; their
        concatenation plus the harvest tail is exactly the masked response
        (pinned by ``tests/test_serve.py`` streaming parity)."""
        if self.spec is None:
            return []
        out: List[tuple] = []
        toks = None
        for slot in range(self.B):
            req = self._slots[slot]
            if req is None or not self._seeded[slot]:
                continue
            n = min(self._steps_bound[slot], self.N)
            if n <= 0:
                continue
            if toks is None:
                toks = np.asarray(self.state.tokens)  # one device fetch
            out.append((req.index, req.meta, toks[slot, :n].copy()))
        return out

    def _trace_request(
        self, req: "_Request", slot: int, t_harvest: float, gen_len: float = 0.0
    ) -> None:
        """Emit the request's lifecycle spans (queue wait → prefill →
        decode, closed by harvest) on this slot's track — a slot holds one
        request at a time, so per-slot tracks never overlap and a stalled
        generation is attributable to its exact row in the merged trace."""
        if self._tracer is None or req.t_refill1 <= 0.0:
            return
        track = f"engine/slot{slot}"
        self._tracer.add_complete_event(
            "engine/queue_wait", req.t_enqueue, req.t_refill0,
            track=track, index=req.index,
        )
        self._tracer.add_complete_event(
            "engine/prefill", req.t_refill0, req.t_refill1,
            track=track, index=req.index,
        )
        self._tracer.add_complete_event(
            "engine/decode", req.t_refill1, t_harvest,
            track=track, index=req.index,
        )
        if self._gamma:
            # the request's decode window IS draft-propose/verify rounds:
            # one span per request, so a low-acceptance straggler is
            # attributable to its exact row in the merged trace
            self._tracer.add_complete_event(
                "engine/spec_verify", req.t_refill1, t_harvest,
                track=track, index=req.index,
                gamma=self._gamma, tokens=gen_len,
            )

    def step(self) -> List[CompletedSequence]:
        """One admit → prefill-span → segment → harvest turn; returns newly
        completed sequences (possibly empty while long rows keep decoding).
        With ``prefill_chunk`` set, the prefill work this step runs is at
        most one chunk per still-prefilling slot, so live decode slots are
        never stalled longer than one chunk's prefill before their next
        segment (the decode-stall gauges measure exactly this)."""
        self._admit()
        if self.spec is not None:
            self._advance_prefill()
        if self._decoding() == 0:
            return []
        if self.spec is not None:
            # reserve writable blocks for the columns this segment may
            # produce, then push the grown tables to device
            if self._ensure_decode_blocks(self.fns.segment_len):
                self._push_tables()
            self._note_block_usage()
        if self._span is not None:
            with self._span(
                "rollout/segment", live=self.live, pending=self.pending
            ) as sp:
                self.state, live_steps, steps = self.fns.decode_segment(
                    self.params, self.state
                )
                sp.fence((self.state.done, self.state.tokens))
            self.stats.decode_s += sp.duration
        else:
            t0 = time.perf_counter()
            self.state, live_steps, steps = self.fns.decode_segment(
                self.params, self.state
            )
            # fetching the step counters below blocks on the segment anyway
        steps = int(np.asarray(steps))
        live_steps = int(np.asarray(live_steps))
        if self._span is None:
            self.stats.decode_s += time.perf_counter() - t0
        self.stats.segments += 1
        self.stats.decode_steps += steps
        self.stats.slot_steps += steps * self.B
        self.stats.live_slot_steps += live_steps
        if self._gamma:
            cur = self._read_spec_counters()
            self.stats.spec_rounds = cur["rounds"] - self._spec_base["rounds"]
            self.stats.spec_accepted = (
                cur["accepted"] - self._spec_base["accepted"]
            )
            self.stats.spec_live_rounds = (
                cur["live_rounds"] - self._spec_base["live_rounds"]
            )
            self.stats.spec_committed = (
                cur["committed"] - self._spec_base["committed"]
            )
        if self.spec is not None:
            step_np = np.asarray(self.state.step) if self._gamma else None
            for slot in range(self.B):
                if self._slots[slot] is not None and self._seeded[slot]:
                    if self._gamma:
                        # per-row accepted-length divergence: under
                        # speculation rows advance different amounts per
                        # round, and the device step counter IS each row's
                        # true committed length
                        self._steps_bound[slot] = int(step_np[slot])
                    else:
                        self._steps_bound[slot] = min(
                            self.N, self._steps_bound[slot] + steps
                        )
        return self._harvest()
