"""Slot-refill decode: the device half of continuous-batching rollouts.

The plain sampler (``ops/sampling.py::generate``) runs a whole ``[B]`` batch
until the *longest* row finishes — every early-EOS row burns decode steps as
padding, and nothing reaches the host until the chunk drains. Here decode is
restructured into fixed-size **segments** over per-slot state: one compiled
program with static shapes, reused across segments. After each segment the
host harvests finished slots and refills them with fresh prompts via an
on-demand prefill into the freed KV-cache rows, so the device batch stays
full while the prompt queue lasts (PipelineRL, arXiv:2509.19128; OPPO,
arXiv:2509.25762).

Bit-parity contract (pinned by ``tests/test_continuous_batching.py``): under
per-row RNG (``GenerationConfig.per_row_rng``) every sequence's tokens /
logprobs / values / mask are **bit-identical** to what plain ``generate``
produces for that prompt at the same padded prompt width and batch size.
The ingredients:

- per-row key chains (``sampling.per_row_keys`` / ``split_row_keys``): a
  row's sample stream depends only on (its key, its step), never on batch
  composition or slot position;
- per-slot ``cache_index`` vectors (the machinery the speculative path
  already drove through ``models/transformer.py::Attention``): slots decode
  at different depths inside one forward;
- the refill is gather-prefill-scatter: only the ``R`` fresh prompts run a
  prefill forward (same structure as plain ``generate``'s prefill —
  ``logits_span=(P-1, P)``, slot-mask attention — at power-of-two bucket
  batch sizes), then scatter into the freed slots with drop-mode indexing.
  Total refill cost over a collection is the serial path's prefill cost
  (every prompt prefills exactly once), NOT a full-batch forward per refill
  event. Rows are row-independent in every dense op, so a row's prefill
  output is bit-identical across batch sizes (pinned by the parity tests);
- finished slots freeze (no buffer/step/rng writes), so harvested rows are
  exactly what the plain loop would have produced, and refilling later
  cannot disturb them.

Cache backends: the decode/refill programs are generic over where the KV
actually lives. The default (dense) backend keeps the historical per-slot
``[B, S]`` cache byte-for-byte. With ``paged=PagedSpec(...)`` the
persistent state is a block pool + per-slot block tables
(``ops/paged_kv.py``): each program gathers the pool into the exact dense
view the model consumes, runs the *unchanged* dense compute, and scatters
the written span back — so paged decode is bit-identical to dense decode
by construction (``tests/test_engine.py``). With
``decode_kernel="pallas"`` the paged *decode segments* skip the gather
entirely: the in-place Pallas paged-attention kernel + fused sampling
(``ops/paged_attention.py``) read and write K/V through the block table,
bit-identical to the gather path (``tests/test_paged_attention.py``). The paged refill additionally
supports a static ``hit`` offset: rows whose leading ``hit`` cache columns
are already committed (prefix-cache hits, ``trlx_tpu/engine/``) prefill
only their unshared suffix ``[hit, P)`` — the suffix forward attends to
the shared blocks through the gathered view, reproducing the full
prefill's values bit-for-bit.

Host-side orchestration (queue, harvest order, block allocation, stats)
lives in ``trlx_tpu/engine/core.py`` (re-exported for compatibility from
``trlx_tpu/pipeline/continuous_batching.py``).
"""

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from trlx_tpu.ops import cache_layout
from trlx_tpu.ops.paged_kv import (
    PagedKV,
    PagedSpec,
    attach_block_table,
    detach_block_table,
    gather_view,
    init_paged_kv,
    scatter_span,
    scatter_steps,
)
from trlx_tpu.ops.sampling import (
    GenerationConfig,
    last_step_info,
    sample_token_from_logits,
    split_row_keys,
)
from trlx_tpu.ops.speculative import spec_round_step

__all__ = ["SlotState", "SpecState", "SlotRefillFns", "make_slot_refill_fns"]


class SlotState(NamedTuple):
    """Per-slot decode state threaded through refill/segment programs.

    ``B`` slots over a ``[B, S = P + N]`` KV cache; all leaves static-shaped
    so one compiled segment program serves the whole collection."""

    tokens: jax.Array  # [B, N] response tokens (pad after eos)
    logprobs: jax.Array  # [B, N] behavior logprobs
    values: jax.Array  # [B, N] value-head outputs (0 if no head)
    mask: jax.Array  # [B, N] 1 on real response tokens (incl. eos)
    slot_mask: jax.Array  # [B, S] attention slot mask over the cache
    cache: Any  # KV cache pytree ([B, S, ...] or scanned [L, B, S, ...])
    logits: jax.Array  # [B, V] logits feeding the next sample
    step_out: Any  # last-position model-output views (adjust_logits hook)
    prompt_len: jax.Array  # [B] real (unpadded) prompt lengths
    done: jax.Array  # [B] finished (or empty) slots — frozen in decode
    step: jax.Array  # [B] per-slot decode step
    rng: jax.Array  # [B, 2] per-slot key chains


class SpecState(NamedTuple):
    """Per-slot state of the *speculative* decode segments
    (``speculative=k`` in :func:`make_slot_refill_fns`).

    The shape geometry is solo ``generate_speculative``'s, per slot: token
    buffers are ``[B, NB = N + G + 1]`` (a round's commit block never
    clips), the target cache spans ``S = P + N + G`` slots *per row*
    through the paged block table, and the draft keeps its own small dense
    ``[B, S]`` cache right in the state (the draft has no prefix sharing —
    paging it would buy nothing and cost a gather per proposal). Field
    names shared with :class:`SlotState` (``tokens``/``logprobs``/
    ``values``/``mask``/``done``/``step``/``cache``/``rng``/``prompt_len``)
    keep the host engine's harvest/refill bookkeeping backend-agnostic;
    ``step`` counts COMMITTED tokens (rows advance unevenly — the engine
    reads it per row instead of assuming uniform segment advancement)."""

    tokens: jax.Array  # [B, NB] response tokens (pad after eos)
    logprobs: jax.Array  # [B, NB] behavior logprobs (target's)
    values: jax.Array  # [B, NB] value-head outputs (0 if no head)
    mask: jax.Array  # [B, NB] 1 on real response tokens (incl. eos)
    prompt_mask: jax.Array  # [B, P] the rows' prompt masks (round masks
    # are rebuilt from this + step every round, like solo)
    cache: Any  # target PagedKV: block pool + per-slot tables, S columns
    d_cache: Any  # draft dense KV cache pytree ([B, S, ...] / scanned)
    t_last: jax.Array  # [B] last committed token (re-fed every round)
    prompt_len: jax.Array  # [B] real (unpadded) prompt lengths
    done: jax.Array  # [B] finished (or empty) slots — frozen
    step: jax.Array  # [B] committed generated tokens (solo's n_out)
    rng: jax.Array  # [B, 2] per-slot key chains
    # cumulative acceptance accounting (absolute counters — the engine
    # differences them across segments for its gauges)
    rounds: jax.Array  # [] spec rounds run
    accepted: jax.Array  # [] accepted draft tokens (pre-truncation)
    live_rounds: jax.Array  # [] live row-rounds
    committed: jax.Array  # [] committed tokens (post budget/eos clip)


class SlotRefillFns(NamedTuple):
    """The compiled slot-refill programs + static shape info."""

    init_state: Callable[[], SlotState]  # fresh all-empty state (host-cheap)
    # (params, state, ids [r,P], mask [r,P], slot_idx [r], keys [r,2]
    #  [, table_rows [r,TB], hit]) — host wrapper that pads r to a
    # power-of-two bucket and dispatches the cached compiled program for
    # that (bucket, hit) pair
    refill_rows: Callable[..., SlotState]
    refill_program: Callable[..., Callable]  # (bucket[, hit]) → compiled fn
    prewarm: Callable[[Any, SlotState], SlotState]  # once-per-fns bucket warmup
    decode_segment: Callable[..., Tuple[SlotState, jax.Array, jax.Array]]
    batch_size: int
    prompt_len: int  # padded prompt width P (fixed per engine)
    max_new_tokens: int
    segment_len: int = 8  # decode steps per compiled segment
    paged: Optional[PagedSpec] = None  # None = dense per-slot cache
    decode_kernel: str = "xla"  # "pallas" = in-place paged decode kernel
    prefill_kernel: str = "xla"  # "pallas" = in-place paged prefill kernel
    # chunked-prefill programs (paged only): prefill a mid-prompt span
    # [start, end) with end < P — cache-only, no SlotState row scatter
    # (the final span [start, P) is the ordinary refill program)
    prefill_chunk_rows: Optional[Callable[..., SlotState]] = None
    prefill_chunk_program: Optional[Callable[..., Callable]] = None
    # speculative decode segments (0 = plain): each segment runs up to
    # ``segment_len`` draft-propose/verify/accept ROUNDS, committing up to
    # ``speculative + 1`` tokens per live row per round. The programs then
    # take ``params = (target_params, draft_params)``.
    speculative: int = 0


def _row_where(flag: jax.Array, new: Any, old: Any) -> Any:
    """Masked per-row merge for a pytree of ``[B, ...]`` leaves (batch axis
    first). Scalar/None leaves pass through untouched."""
    B = flag.shape[0]

    def merge(n, o):
        if n is None or not hasattr(n, "ndim") or n.ndim == 0:
            return n
        return jnp.where(flag.reshape((B,) + (1,) * (n.ndim - 1)), n, o)

    return jax.tree_util.tree_map(merge, new, old, is_leaf=lambda x: x is None)


def _row_set(buf: jax.Array, val: jax.Array, col: jax.Array, live: jax.Array) -> jax.Array:
    """Write ``val[i]`` into ``buf[i, col[i]]`` for live rows; frozen rows
    keep their buffer untouched (a finished-but-unharvested slot must never
    be clobbered by clamped out-of-range writes)."""
    written = jax.vmap(
        lambda row, v, c: jax.lax.dynamic_update_slice(row, v[None], (c,))
    )(buf, val.astype(buf.dtype), col)
    return jnp.where(live[:, None], written, buf)


def make_slot_refill_fns(
    apply_fn: Callable[..., Dict[str, Any]],
    init_cache_fn: Callable[[int, int], Any],
    batch_size: int,
    prompt_len: int,
    config: GenerationConfig,
    adjust_logits: Optional[Callable[[Dict[str, Any], jax.Array], jax.Array]] = None,
    segment_len: int = 8,
    params_example: Any = None,
    jit: bool = True,
    paged: Optional[PagedSpec] = None,
    decode_kernel: str = "xla",
    prefill_kernel: str = "xla",
    speculative: int = 0,
    draft_apply: Optional[Callable[..., Dict[str, Any]]] = None,
    init_draft_cache_fn: Optional[Callable[[int, int], Any]] = None,
    transition_mask: Optional[jax.Array] = None,
) -> SlotRefillFns:
    """Build the (jitted) slot-refill programs for one shape bucket.

    ``apply_fn(params, input_ids, attention_mask, positions, cache,
    cache_index, ...)`` is the model wrappers' ``__call__``;
    ``params_example`` (real params or ShapeDtypeStructs) is needed once to
    shape the ``step_out`` carry of the empty state via ``eval_shape`` —
    nothing is executed. ``config.per_row_rng`` must be True: slot migration
    is only stream-invariant under per-row key chains.

    ``paged`` switches the KV backend to a block pool + per-slot block
    tables (``ops/paged_kv.py``); the refill and segment programs then take
    their block-table rows from the host allocator (``trlx_tpu/engine/``)
    and gather/scatter around the unchanged dense compute.

    ``decode_kernel`` selects the paged *decode-segment* compute
    (``engine.decode_kernel``): ``"xla"`` is the gather → dense compute →
    scatter reference; ``"pallas"`` runs the in-place paged-attention
    decode kernel + fused sampling (``ops/paged_attention.py``) — K/V read
    and written through the block table with no transient dense view.
    Bit-identical to the gather path by contract
    (``tests/test_paged_attention.py``).

    ``prefill_kernel`` selects the paged *refill prefill* compute
    (``engine.prefill_kernel``): ``"xla"`` is the gather → dense prefill →
    scatter reference; ``"pallas"`` runs the in-place paged-prefill kernel
    (``ops/paged_prefill.py`` via ``models/transformer.py``) — the chunk's
    K/V committed through the block table with no dense view on entry and
    no scatter on exit, bit-identical to the gather path by contract.
    With it (or without — the chunk programs exist for both flavors), the
    ``prefill_chunk_rows`` programs prefill a mid-prompt span
    ``[start, end)``, ``end < P``, committing K/V only: the host engine
    interleaves these with decode segments (``engine.prefill_chunk``) so a
    long prompt never stalls live decode slots longer than one chunk.

    ``speculative = k > 0`` (``engine.speculative``) swaps the decode
    segment for the *speculative* segment: each segment runs up to
    ``segment_len`` draft-propose → verify → accept ROUNDS of
    :func:`trlx_tpu.ops.speculative.spec_round_step` — literally the solo
    sampler's round body, so every slot's token stream is bit-identical to
    a solo ``generate_speculative`` run with that row's key chain,
    regardless of batch composition or refills. Requires the paged backend
    (the verify writes flow through the block table with drop-mode
    commits), per-row RNG, plus ``draft_apply`` / ``init_draft_cache_fn``
    for the proposal model. Both kernel flavors compose: ``decode_kernel:
    pallas`` runs the rounds in place — each verify forward commits its
    ``G + 1`` probe columns through per-row (done-poisoned) block tables
    and reads K/V via the multi-position verify kernel
    (``ops/paged_attention.py::paged_verify_attention``) — while ``xla``
    keeps the gather → rounds → scatter reference shape. ``transition_mask``
    (the trainer's logit mask) must be passed HERE rather than composed
    into ``adjust_logits``: the rounds apply it to draft proposals and
    target verify distributions separately, exactly like solo.
    ``params`` for every program becomes ``(target_params, draft_params)``
    — one tuple, so mid-stream ``swap_params`` swaps both atomically.
    """
    if decode_kernel not in ("xla", "pallas"):
        raise ValueError(
            f"unknown decode_kernel '{decode_kernel}' (xla | pallas)"
        )
    if decode_kernel == "pallas" and paged is None:
        raise ValueError(
            "decode_kernel: pallas is the in-place *paged* decode kernel — "
            "it requires the paged KV backend (engine.backend: paged)"
        )
    if prefill_kernel not in ("xla", "pallas"):
        raise ValueError(
            f"unknown prefill_kernel '{prefill_kernel}' (xla | pallas)"
        )
    if prefill_kernel == "pallas" and paged is None:
        raise ValueError(
            "prefill_kernel: pallas is the in-place *paged* prefill kernel "
            "(ops/paged_prefill.py) — it requires the paged KV backend "
            "(engine.backend: paged)"
        )
    G = int(speculative or 0)
    if G < 0:
        raise ValueError(f"speculative must be >= 0, got {G}")
    if G:
        if paged is None:
            raise ValueError(
                "speculative decode segments require the paged KV backend "
                "(engine.backend: paged) — the verify pass commits accepted "
                "K/V through the block table with drop-mode writes"
            )
        if draft_apply is None or init_draft_cache_fn is None:
            raise ValueError(
                "speculative decode segments need the draft model: pass "
                "draft_apply and init_draft_cache_fn "
                "(model.draft_model_path resolves them in the trainer)"
            )
        if not config.per_row_rng:
            raise ValueError(
                "engine.speculative requires per-row RNG chains "
                "(GenerationConfig.per_row_rng=True): speculative slot "
                "streams are only batch-composition-invariant when draft "
                "proposals, acceptance uniforms, and residual/bonus draws "
                "advance [B, 2] per-row key chains"
            )
    if not config.per_row_rng:
        config = dataclasses.replace(config, per_row_rng=True)
    B, P, N = batch_size, prompt_len, config.max_new_tokens
    # speculative geometry is solo's: commits cap at P+N but each round
    # probes G slots past the last commit, and the key width must match
    # solo's exactly (see spec_round_step / _make_prefill_chunk's
    # key-width lowering note) — G = 0 reduces to the plain S = P + N
    S = P + N + G
    NB = N + G + 1  # spec token buffers: block writes never clip
    cache_layout.refuse(jax.eval_shape(lambda: init_cache_fn(1, S)), "slot_refill" if paged is None else "engine", S)

    def empty_state() -> SlotState:
        # step_out structure comes from an abstract prefill — shapes only
        # (the dense [B, S] cache inside eval_shape never materializes,
        # which matters for the paged backend: its persistent state is the
        # block pool, not a dense cache)
        out_sds = jax.eval_shape(
            lambda p: apply_fn(
                p,
                jnp.zeros((B, P), jnp.int32),
                attention_mask=jnp.zeros((B, S), jnp.int32),
                positions=None,
                cache=init_cache_fn(B, S),
                cache_index=jnp.asarray(0, jnp.int32),
                logits_span=(P - 1, P),
            ),
            params_example,
        )
        step_out = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape[:1] + s.shape[2:], s.dtype),
            last_step_info_abstract(out_sds),
        )
        step_out["last_tokens"] = jnp.zeros((B,), jnp.int32)
        logits_sds = out_sds["logits"]
        cache = (
            init_paged_kv(init_cache_fn, paged, B, S)
            if paged is not None
            else init_cache_fn(B, S)
        )
        return SlotState(
            tokens=jnp.full((B, N), config.pad_token_id, jnp.int32),
            logprobs=jnp.zeros((B, N), jnp.float32),
            values=jnp.zeros((B, N), jnp.float32),
            mask=jnp.zeros((B, N), jnp.int32),
            slot_mask=jnp.zeros((B, S), jnp.int32),
            cache=cache,
            # native model dtype: plain generate carries raw logits, and the
            # adjust-logits hook must see identical bits in both samplers
            logits=jnp.zeros((B, logits_sds.shape[-1]), logits_sds.dtype),
            step_out=step_out,
            prompt_len=jnp.zeros((B,), jnp.int32),
            done=jnp.ones((B,), bool),  # empty slots never decode
            step=jnp.zeros((B,), jnp.int32),
            rng=jnp.zeros((B, 2), jnp.uint32),
        )

    def empty_spec_state() -> SpecState:
        # no eval_shape needed: spec segments carry no logits/step_out —
        # every round re-derives both models' distributions by re-feeding
        # the last committed token, exactly like solo
        return SpecState(
            tokens=jnp.full((B, NB), config.pad_token_id, jnp.int32),
            logprobs=jnp.zeros((B, NB), jnp.float32),
            values=jnp.zeros((B, NB), jnp.float32),
            mask=jnp.zeros((B, NB), jnp.int32),
            prompt_mask=jnp.zeros((B, P), jnp.int32),
            cache=init_paged_kv(init_cache_fn, paged, B, S),
            d_cache=init_draft_cache_fn(B, S),
            t_last=jnp.zeros((B,), jnp.int32),
            prompt_len=jnp.zeros((B,), jnp.int32),
            done=jnp.ones((B,), bool),  # empty slots never decode
            step=jnp.zeros((B,), jnp.int32),
            rng=jnp.zeros((B, 2), jnp.uint32),
            rounds=jnp.asarray(0, jnp.int32),
            accepted=jnp.asarray(0, jnp.int32),
            live_rounds=jnp.asarray(0, jnp.int32),
            committed=jnp.asarray(0, jnp.int32),
        )

    def last_step_info_abstract(out_sds: Dict[str, Any]) -> Dict[str, Any]:
        # eval_shape twin of sampling.last_step_info (keeps [B, 1, ...] dims
        # so the zeros() above can drop the per-step axis uniformly)
        from trlx_tpu.ops.sampling import _NON_CARRY_KEYS

        return {
            k: v
            for k, v in out_sds.items()
            if k not in _NON_CARRY_KEYS and v is not None
        }

    def _make_refill(R: int, hit: int = 0):
        def refill(
            params: Any,
            state: SlotState,
            input_ids: jax.Array,  # [R, P] left-padded fresh prompts
            prompt_mask: jax.Array,  # [R, P]
            slot_idx: jax.Array,  # [R] target slots; >= B = padding (dropped)
            new_keys: jax.Array,  # [R, 2] per-row key chains
            table_rows: Optional[jax.Array] = None,  # [R, TB] (paged only)
        ) -> SlotState:
            """Gather-prefill-scatter into freed cache slots: only the ``R``
            refilled rows run the prefill forward (cost ``R·(P − hit)``
            tokens — the serial path's prefill cost amortized over the run,
            minus prefix-cache hits — instead of a full ``B·P`` forward per
            refill event), then scatter into the big state at ``slot_idx``.
            Out-of-range indices (the power-of-two bucket padding) drop:
            every lane write is deterministic, no duplicate-index races.

            With the paged backend and ``hit > 0`` the leading ``hit`` cache
            columns are already committed in shared blocks: only the suffix
            ``[hit, P)`` runs the forward, attending to the shared prefix
            through the gathered dense view — per-query-row independence of
            every dense op makes the suffix's KV/logits bit-identical to a
            full prefill's (the same property the bucket-size invariance
            already relies on)."""
            input_ids = input_ids.astype(jnp.int32)
            prompt_mask = prompt_mask.astype(jnp.int32)
            slot_mask_r = jnp.concatenate(
                [prompt_mask, jnp.zeros((R, N), jnp.int32)], axis=1
            )
            if paged is not None and prefill_kernel == "pallas":
                # in-place paged prefill (ops/paged_prefill.py via the
                # model's paged branch): the suffix's K/V commits through
                # the table and attention reads pool blocks straight into
                # VMEM — no dense view exists, before or after. Committed
                # prefix blocks (hit > 0, or earlier prefill chunks) are
                # read in place; everything else is bias-masked to an
                # exact-0.0 softmax contribution.
                row_cache = attach_block_table(state.cache.pool, table_rows)
            elif paged is not None and hit > 0:
                # dense view of the refilled rows: shared prefix blocks hold
                # committed values; everything else reads the zero block or
                # recycled slots the mask keeps out of attention (masked
                # scores underflow softmax to exactly 0.0, same as the
                # dense cache's zeros)
                row_cache = gather_view(state.cache.pool, table_rows, S)
            else:
                # cold refill (dense, or paged with no committed prefix):
                # the forward writes every prompt column itself and the
                # response region is masked — a zero cache is equivalent
                # and skips the pool gather entirely
                row_cache = init_cache_fn(R, S)
            out = apply_fn(
                params,
                input_ids[:, hit:],
                attention_mask=slot_mask_r,
                positions=None,
                cache=row_cache,
                cache_index=jnp.asarray(hit, jnp.int32),
                logits_span=(P - hit - 1, P - hit),
            )
            step_out_r = {**last_step_info(out), "last_tokens": input_ids[:, -1]}

            def scat(big, rows):
                if big is None or not hasattr(big, "ndim") or big.ndim == 0:
                    return big
                return big.at[slot_idx].set(rows.astype(big.dtype), mode="drop")

            def scat_cache(big, rows):
                if big.ndim - 4 == 0:
                    return big.at[slot_idx].set(rows.astype(big.dtype), mode="drop")
                # scanned layout [L, B, S, KV, D]: batch axis 1
                return big.at[:, slot_idx].set(rows.astype(big.dtype), mode="drop")

            if paged is not None:
                if prefill_kernel == "pallas":
                    # the forward already committed the span [hit, P) into
                    # the pool through the table (drop-mode writes inside
                    # the model's paged branch) — nothing to scatter
                    new_pool = detach_block_table(out["cache"])
                else:
                    # commit the recomputed span [hit, P) from the dense view
                    new_pool = scatter_span(
                        state.cache.pool, table_rows, out["cache"], hit, P - hit
                    )
                new_cache = PagedKV(
                    pool=new_pool,
                    block_table=state.cache.block_table.at[slot_idx].set(
                        table_rows, mode="drop"
                    ),
                )
            else:
                new_cache = jax.tree_util.tree_map(
                    scat_cache, state.cache, out["cache"]
                )

            tree_scat = lambda big, rows: jax.tree_util.tree_map(  # noqa: E731
                scat, big, rows, is_leaf=lambda x: x is None
            )
            return SlotState(
                tokens=scat(state.tokens, jnp.full((R, N), config.pad_token_id, jnp.int32)),
                logprobs=scat(state.logprobs, jnp.zeros((R, N), jnp.float32)),
                values=scat(state.values, jnp.zeros((R, N), jnp.float32)),
                mask=scat(state.mask, jnp.zeros((R, N), jnp.int32)),
                slot_mask=scat(state.slot_mask, slot_mask_r),
                cache=new_cache,
                logits=scat(state.logits, out["logits"][:, -1, :]),
                step_out=tree_scat(state.step_out, step_out_r),
                prompt_len=scat(state.prompt_len, jnp.sum(prompt_mask, axis=1)),
                done=scat(state.done, jnp.zeros((R,), bool)),
                step=scat(state.step, jnp.zeros((R,), jnp.int32)),
                rng=scat(state.rng, new_keys),
            )

        return refill

    def _make_spec_refill(R: int, hit: int = 0):
        def refill(
            params: Any,  # (target_params, draft_params)
            state: SpecState,
            input_ids: jax.Array,  # [R, P] left-padded fresh prompts
            prompt_mask: jax.Array,  # [R, P]
            slot_idx: jax.Array,  # [R] target slots; >= B = padding (dropped)
            new_keys: jax.Array,  # [R, 2] per-row key chains
            table_rows: Optional[jax.Array] = None,  # [R, TB]
        ) -> SpecState:
            """The speculative twin of ``_make_refill``: prefill the TARGET
            suffix ``[hit, P)`` through the block table exactly like the
            plain paged refill (same forward, same ``scatter_span`` commit
            — K/V only, ``logits_span=(0, 0)``: the first round re-feeds
            the last prompt token, so prefill logits are never consumed),
            plus a full ``[0, P)`` DRAFT prefill on a fresh zero cache
            scattered whole-row into ``state.d_cache`` (the draft shares
            nothing across rows — prefix hits only skip target compute;
            the full-row scatter also zeroes any stale recycled-slot
            columns past ``P``). Both prefills use solo's ``S``-wide slot
            mask, so the refilled row's caches are bit-identical to a solo
            run's post-prefill caches. ``prefill_kernel: pallas`` commits
            the target suffix through the block table in place
            (``ops/paged_prefill.py`` via the model's paged branch) —
            same forward, no gather on entry, no scatter on exit."""
            t_params, d_params = params
            input_ids = input_ids.astype(jnp.int32)
            prompt_mask = prompt_mask.astype(jnp.int32)
            slot_mask_r = jnp.concatenate(
                [prompt_mask, jnp.zeros((R, S - P), jnp.int32)], axis=1
            )
            if prefill_kernel == "pallas":
                row_cache = attach_block_table(state.cache.pool, table_rows)
            elif hit > 0:
                row_cache = gather_view(state.cache.pool, table_rows, S)
            else:
                row_cache = init_cache_fn(R, S)
            t_out = apply_fn(
                t_params,
                input_ids[:, hit:],
                attention_mask=slot_mask_r,
                positions=None,
                cache=row_cache,
                cache_index=jnp.asarray(hit, jnp.int32),
                logits_span=(0, 0),
            )
            if prefill_kernel == "pallas":
                # the forward already committed [hit, P) through the table
                new_pool = detach_block_table(t_out["cache"])
            else:
                new_pool = scatter_span(
                    state.cache.pool, table_rows, t_out["cache"], hit, P - hit
                )
            new_cache = PagedKV(
                pool=new_pool,
                block_table=state.cache.block_table.at[slot_idx].set(
                    table_rows, mode="drop"
                ),
            )
            d_out = draft_apply(
                d_params,
                input_ids,
                attention_mask=slot_mask_r,
                positions=None,
                cache=init_draft_cache_fn(R, S),
                cache_index=jnp.asarray(0, jnp.int32),
                logits_span=(0, 0),
            )

            def scat(big, rows):
                if big is None or not hasattr(big, "ndim") or big.ndim == 0:
                    return big
                return big.at[slot_idx].set(rows.astype(big.dtype), mode="drop")

            def scat_cache(big, rows):
                if big.ndim - 4 == 0:
                    return big.at[slot_idx].set(rows.astype(big.dtype), mode="drop")
                # scanned layout [L, B, S, KV, D]: batch axis 1
                return big.at[:, slot_idx].set(rows.astype(big.dtype), mode="drop")

            return SpecState(
                tokens=scat(
                    state.tokens, jnp.full((R, NB), config.pad_token_id, jnp.int32)
                ),
                logprobs=scat(state.logprobs, jnp.zeros((R, NB), jnp.float32)),
                values=scat(state.values, jnp.zeros((R, NB), jnp.float32)),
                mask=scat(state.mask, jnp.zeros((R, NB), jnp.int32)),
                prompt_mask=scat(state.prompt_mask, prompt_mask),
                cache=new_cache,
                d_cache=jax.tree_util.tree_map(
                    scat_cache, state.d_cache, d_out["cache"]
                ),
                t_last=scat(state.t_last, input_ids[:, -1]),
                prompt_len=scat(state.prompt_len, jnp.sum(prompt_mask, axis=1)),
                done=scat(state.done, jnp.zeros((R,), bool)),
                step=scat(state.step, jnp.zeros((R,), jnp.int32)),
                rng=scat(state.rng, new_keys),
                rounds=state.rounds,
                accepted=state.accepted,
                live_rounds=state.live_rounds,
                committed=state.committed,
            )

        return refill

    _refill_cache: Dict[Tuple[int, int], Callable] = {}
    _warmed = {"done": False}

    def refill_program(bucket: int, hit: int = 0) -> Callable:
        """The compiled refill program for one (power-of-two bucket size,
        prefix-hit offset) pair. ``hit`` is always 0 on the dense backend;
        paged prefix-cache hits compile one extra variant per distinct
        block-aligned hit length, on first use."""
        if (bucket, hit) not in _refill_cache:
            fn = (_make_spec_refill if G else _make_refill)(bucket, hit)
            _refill_cache[(bucket, hit)] = jax.jit(fn) if jit else fn
        return _refill_cache[(bucket, hit)]

    def _make_prefill_chunk(R: int, start: int, end: int):
        def prefill_chunk(
            params: Any,
            state: SlotState,
            input_ids: jax.Array,  # [R, P] left-padded fresh prompts
            prompt_mask: jax.Array,  # [R, P]
            table_rows: jax.Array,  # [R, TB] the rows' block tables
        ) -> SlotState:
            """Prefill the mid-prompt span ``[start, end)`` of ``R`` rows,
            committing K/V into their pool blocks only — no logits, no
            SlotState row scatter (the rows stay empty/done until the final
            span ``[x, P)`` runs the ordinary refill program and seeds the
            sampler). Keys keep the FULL cache width ``S`` with columns
            ``>= end`` masked out: not-yet-prefilled (and response-region)
            columns contribute exact-0.0 softmax terms, and keeping the
            key width identical to the monolithic pass's keeps the score
            dots' shapes identical too — truncating the key axis changes
            the dot's lowering at some shapes (1-ulp contraction drift,
            same genre as the kernel's batch-dim landmine), which would
            break the chunked ≡ unchunked bit-parity the suite pins.
            Tables are taken as an argument (host mirror) — the device
            block-table rows of still-prefilling slots are stale by
            design."""
            # speculative builds chunk only the TARGET's prompt (the draft
            # prefills whole at refill time — it is the small model; only
            # the target's prefill can stall live decode slots)
            t_params = params[0] if G else params
            input_ids = input_ids.astype(jnp.int32)
            prompt_mask = prompt_mask.astype(jnp.int32)
            # visibility: committed prompt columns [0, end) only
            span_mask = prompt_mask * (jnp.arange(P)[None, :] < end)
            key_mask = jnp.concatenate(
                [span_mask, jnp.zeros((R, S - P), jnp.int32)], axis=1
            )
            if prefill_kernel == "pallas":
                row_cache = attach_block_table(state.cache.pool, table_rows)
            elif start > 0:
                row_cache = gather_view(state.cache.pool, table_rows, S)
            else:
                # first chunk: nothing committed below column 0 — a zero
                # cache is equivalent and skips the gather (the cold-refill
                # shortcut)
                row_cache = init_cache_fn(R, S)
            out = apply_fn(
                t_params,
                input_ids[:, start:end],
                attention_mask=key_mask,
                positions=None,
                cache=row_cache,
                cache_index=jnp.asarray(start, jnp.int32),
                logits_span=(0, 0),  # mid-prompt: no sampler to seed
            )
            if prefill_kernel == "pallas":
                pool = detach_block_table(out["cache"])
            else:
                pool = scatter_span(
                    state.cache.pool, table_rows, out["cache"], start,
                    end - start,
                )
            return state._replace(
                cache=PagedKV(pool, state.cache.block_table)
            )

        return prefill_chunk

    _chunk_cache: Dict[Tuple[int, int, int], Callable] = {}

    def prefill_chunk_program(bucket: int, start: int, end: int) -> Callable:
        """The compiled mid-chunk prefill program for one (bucket, span)
        triple. Spans are engine-aligned to absolute multiples of the
        chunk size (plus block-aligned prefix-hit starts), so the variant
        count stays bounded; they compile lazily on first use — their set
        depends on the prompt stream and ``engine.prefill_chunk``."""
        if paged is None:
            raise ValueError(
                "chunked prefill requires the paged KV backend "
                "(engine.backend: paged) — dense per-slot caches have no "
                "span-committing chunk program"
            )
        if not 0 <= start < end < P:
            raise ValueError(
                f"mid-chunk span [{start}, {end}) must sit strictly inside "
                f"the prompt region [0, {P}) — the final span is the "
                "refill program"
            )
        if (bucket, start, end) not in _chunk_cache:
            fn = _make_prefill_chunk(bucket, start, end)
            _chunk_cache[(bucket, start, end)] = jax.jit(fn) if jit else fn
        return _chunk_cache[(bucket, start, end)]

    def prefill_chunk_rows(
        params: Any,
        state: SlotState,
        input_ids: Any,  # [r, P] host or device rows, r <= B
        prompt_mask: Any,
        table_rows: Any,  # [r, TB]
        start: int,
        end: int,
    ) -> SlotState:
        """Host wrapper for one mid-chunk span: the shared bucket+pad
        protocol (``_bucket_pad`` — padding rows carry all-out-of-range
        tables, so their commits drop), then the cached compiled program."""
        bucket, _, input_ids, prompt_mask, table_rows = _bucket_pad(
            input_ids, prompt_mask, table_rows
        )
        return prefill_chunk_program(bucket, start, end)(
            params,
            state,
            jnp.asarray(input_ids),
            jnp.asarray(prompt_mask),
            jnp.asarray(table_rows),
        )

    def prewarm(params: Any, state: SlotState) -> SlotState:
        """Compile every cold (hit = 0) refill bucket with dropped no-op
        calls (all ``slot_idx = B``) so a collection's completion pattern
        never triggers a mid-run XLA compile. Runs ONCE per fns — these
        programs are cached per shape bucket, so later engines over the
        same fns (one per ``make_experience`` call) skip straight through
        instead of re-executing ~2·B·P tokens of dead prefill every
        collection. Prefix-hit variants (paged) compile lazily on first
        hit: their set depends on the prompt stream.

        The no-op results thread through ``state`` (content unchanged —
        every write drops): jit's executable cache keys on input *placement*
        as well as avals, and real refill calls always see computed
        (committed) state leaves. The first bucket runs twice so even it
        gets a committed-state cache entry."""
        if _warmed["done"]:
            return state
        buckets = [1]
        while buckets[-1] < B:
            buckets.append(min(buckets[-1] * 2, B))
        for bucket in [buckets[0]] + buckets:
            args = [
                params,
                state,
                jnp.full((bucket, P), config.pad_token_id, jnp.int32),
                jnp.zeros((bucket, P), jnp.int32),
                jnp.full((bucket,), B, jnp.int32),  # out of range: drop
                jnp.zeros((bucket, 2), jnp.asarray(state.rng).dtype),
            ]
            if paged is not None:
                TB = state.cache.block_table.shape[1]
                # out-of-range block ids: gathers clamp to a lane the zero
                # slot mask hides, scatters drop — a true no-op
                args.append(jnp.full((bucket, TB), paged.max_blocks, jnp.int32))
            state = refill_program(bucket)(*args)
        _warmed["done"] = True
        return state

    def _bucket_pad(input_ids: Any, prompt_mask: Any, table_rows: Any):
        """The shared bucket+pad protocol behind the refill and chunk host
        wrappers: round ``r`` up to the next power-of-two bucket; padding
        rows carry pad tokens, all-zero masks, and ``max_blocks``-poisoned
        block tables (every commit drops). Returns
        ``(bucket, pad, input_ids, prompt_mask, table_rows)``."""
        import numpy as np

        input_ids = np.asarray(input_ids, np.int32)
        prompt_mask = np.asarray(prompt_mask, np.int32)
        if table_rows is not None:
            table_rows = np.asarray(table_rows, np.int32)
        r = input_ids.shape[0]
        bucket = 1
        while bucket < r:
            bucket *= 2
        bucket = min(bucket, max(B, 1))
        if bucket < r:  # r > B cannot happen (more rows than slots)
            raise ValueError(f"refilling {r} rows into {B} slots")
        pad = bucket - r
        if pad:
            input_ids = np.concatenate(
                [input_ids, np.full((pad, P), config.pad_token_id, np.int32)]
            )
            prompt_mask = np.concatenate(
                [prompt_mask, np.zeros((pad, P), np.int32)]
            )
            if table_rows is not None:
                table_rows = np.concatenate(
                    [
                        table_rows,
                        np.full(
                            (pad, table_rows.shape[1]), paged.max_blocks,
                            np.int32,
                        ),
                    ]
                )
        return bucket, pad, input_ids, prompt_mask, table_rows

    def refill_rows(
        params: Any,
        state: SlotState,
        input_ids: Any,  # [r, P] host or device rows, r <= B
        prompt_mask: Any,
        slot_idx: Any,  # [r] distinct target slots
        new_keys: Any,
        table_rows: Any = None,  # [r, TB] block-table rows (paged only)
        hit: int = 0,  # committed leading cache columns (block-aligned)
    ) -> SlotState:
        """Host wrapper: round ``r`` up to the next power-of-two bucket
        (padding rows carry ``slot_idx = B`` and scatter-drop), so at most
        ``log2(B)+1`` refill programs ever compile per hit length while the
        prefill cost stays within 2× of the rows actually refilled."""
        import numpy as np

        slot_idx = np.asarray(slot_idx, np.int32)
        new_keys = np.asarray(new_keys)
        bucket, pad, input_ids, prompt_mask, table_rows = _bucket_pad(
            input_ids, prompt_mask, table_rows if paged is not None else None
        )
        if pad:
            slot_idx = np.concatenate([slot_idx, np.full((pad,), B, np.int32)])
            new_keys = np.concatenate(
                [new_keys, np.zeros((pad, 2), new_keys.dtype)]
            )
        args = [
            params, state, jnp.asarray(input_ids), jnp.asarray(prompt_mask),
            jnp.asarray(slot_idx), jnp.asarray(new_keys),
        ]
        if paged is not None:
            args.append(jnp.asarray(table_rows))
        return refill_program(bucket, hit)(*args)

    def decode_segment(params: Any, state: SlotState):
        """Up to ``segment_len`` decode steps over live slots; early exit
        when every slot is done. Returns ``(state, live_steps, steps_run)``
        — the utilization numerators/denominators for
        ``throughput/slot_utilization`` / ``rollout/padded_decode_frac``.

        Paged backend, ``decode_kernel: xla`` (the reference): gather the
        pool into the dense view once per segment, run the UNCHANGED dense
        loop on it, scatter each row's live writes (columns
        ``P + step_before .. P + step_after − 1``) back into its table's
        blocks. The loop body literally is the dense body over
        bit-identical values, so paged decode inherits the dense backend's
        bit-parity with plain ``generate``; the view is a per-program
        temporary.

        Paged backend, ``decode_kernel: pallas``: no view, no scatter —
        each step's forward reads K/V through the block table in place and
        commits its one column per live row through the table
        (``ops/paged_attention.py`` via ``models/transformer.py``), with
        fused top-k/top-p/temperature sampling. Frozen rows' table rows
        are poisoned out of range per step, so their dead writes drop —
        exactly the columns ``scatter_steps`` would not have committed.
        Bit-identical to the gather path (tests/test_paged_attention.py,
        tests/test_engine.py)."""
        if G:
            if decode_kernel == "pallas":
                return _spec_decode_segment_paged_kernel(params, state)
            return _spec_decode_segment(params, state)
        if paged is not None and decode_kernel == "pallas":
            return _decode_segment_paged_kernel(params, state)
        if paged is not None:
            paged_cache = state.cache
            view = gather_view(paged_cache.pool, paged_cache.block_table, S)
            step_before = state.step
            st, live_steps, steps = _decode_segment_dense(
                params, state._replace(cache=view)
            )
            pool = scatter_steps(
                paged_cache.pool,
                paged_cache.block_table,
                st.cache,
                P + step_before,
                st.step - step_before,
                segment_len,
            )
            return (
                st._replace(cache=PagedKV(pool, paged_cache.block_table)),
                live_steps,
                steps,
            )
        return _decode_segment_dense(params, state)

    def _spec_decode_segment(params: Any, state: SpecState):
        """Up to ``segment_len`` speculative ROUNDS over live slots — the
        round body is :func:`trlx_tpu.ops.speculative.spec_round_step`,
        shared verbatim with the solo sampler, so per-slot bit-parity is
        structural. One segment = one compiled program: fixed trip count
        (early exit when all slots finish), per-row live masks absorb the
        variable advancement — a row commits between 1 and ``G + 1``
        tokens per live round, bounded by ``segment_len · (G + 1)`` per
        segment — so the bucket never recompiles.

        Paged plumbing mirrors the plain xla segment: ONE pool gather into
        solo's dense ``[B, S]`` view on entry (each round's draft
        proposals, the single width-``G + 1`` target verify forward, and
        acceptance run on the view), ONE ``scatter_steps`` commit on exit.
        The committed span per row is ``[P + step_in − 1, P + step_out]``:
        the re-feed column ``c − 1`` is re-committed (its pool value was
        the residual-sampled token's never-forwarded placeholder — solo's
        dense cache holds the same re-feed result), accepted/bonus columns
        carry the verify's K/V, and REJECTED probe columns simply fall
        outside ``counts`` — ``scatter_steps`` poisons their lanes
        out-of-range exactly like frozen rows', so they drop instead of
        dirtying pool blocks another row may later receive."""
        t_params, d_params = params
        table = state.cache.block_table
        entry_live = ~state.done
        step_in = state.step
        view = gather_view(state.cache.pool, table, S)
        carry = {
            "rng": state.rng,
            "n_out": state.step,
            "done": state.done,
            "t_last": state.t_last,
            "t_cache": view,
            "d_cache": state.d_cache,
            "tokens": state.tokens,
            "logprobs": state.logprobs,
            "values": state.values,
            "mask": state.mask,
            "rounds": state.rounds,
            "accepted": state.accepted,
            "live_rounds": state.live_rounds,
            "committed": state.committed,
        }

        def body(c):
            cr, k = c
            return (
                spec_round_step(
                    cr,
                    prompt_mask=state.prompt_mask,
                    target_apply=apply_fn,
                    target_params=t_params,
                    draft_apply=draft_apply,
                    draft_params=d_params,
                    config=config,
                    G=G,
                    transition_mask=transition_mask,
                    adjust_logits=adjust_logits,
                ),
                k + 1,
            )

        def cond(c):
            cr, k = c
            return (k < segment_len) & ~jnp.all(cr["done"])

        final, _ = jax.lax.while_loop(
            cond, body, (carry, jnp.asarray(0, jnp.int32))
        )
        pool = scatter_steps(
            state.cache.pool,
            table,
            final["t_cache"],
            P + step_in - 1,
            jnp.where(entry_live, final["n_out"] - step_in + 1, 0),
            segment_len * (G + 1) + 1,
        )
        new_state = SpecState(
            tokens=final["tokens"],
            logprobs=final["logprobs"],
            values=final["values"],
            mask=final["mask"],
            prompt_mask=state.prompt_mask,
            cache=PagedKV(pool, table),
            d_cache=final["d_cache"],
            t_last=final["t_last"],
            prompt_len=state.prompt_len,
            done=final["done"],
            step=final["n_out"],
            rng=final["rng"],
            rounds=final["rounds"],
            accepted=final["accepted"],
            live_rounds=final["live_rounds"],
            committed=final["committed"],
        )
        # same (state, live_steps, steps) contract as the plain segment,
        # in ROUND units (slot_utilization keeps its live/total meaning;
        # token-level throughput is the spec_* gauges' job)
        return (
            new_state,
            final["live_rounds"] - state.live_rounds,
            final["rounds"] - state.rounds,
        )

    def _spec_decode_segment_paged_kernel(params: Any, state: SpecState):
        """The in-place twin of ``_spec_decode_segment``: the round body is
        still :func:`trlx_tpu.ops.speculative.spec_round_step` — verbatim —
        but the target cache threaded through it is the block pool with a
        per-round done-poisoned table attached instead of a gathered dense
        view, so each round's width-``G + 1`` verify forward reads K/V via
        the multi-position verify kernel
        (``ops/paged_attention.py::paged_verify_attention``, per-row probe
        windows ``[c − 1, c + G)`` through ``models/transformer.py``'s
        vector-``cache_index`` paged branch) and commits those columns with
        drop-mode writes as it goes. No gather on entry, no
        ``scatter_steps`` on exit.

        Commit discipline vs the gather reference: the re-feed column
        ``c − 1`` is re-written with identical bits (same token, same
        position, same visible columns — the recompute the gather path's
        scatter also re-commits); accepted/bonus columns carry the verify's
        K/V; REJECTED probe columns are written in place where
        ``scatter_steps`` would have dropped them, but they sit strictly
        above every row's committed length, so slot-causal masking keeps
        them invisible to every later read — the same stale-value
        invariant recycled blocks already rely on. Rows that are done at a
        round's START get their table rows poisoned out of range (their
        blocks may already be recycled after harvest), exactly mirroring
        ``_decode_segment_paged_kernel``'s per-step freeze masking. The
        draft cache stays dense per slot — the draft never touches the
        pool."""
        t_params, d_params = params
        table = state.cache.block_table
        carry = {
            "rng": state.rng,
            "n_out": state.step,
            "done": state.done,
            "t_last": state.t_last,
            # the carry holds the BARE pool (stable pytree across rounds);
            # each round attaches a freshly poisoned table before the
            # shared round body and strips it after
            "t_cache": state.cache.pool,
            "d_cache": state.d_cache,
            "tokens": state.tokens,
            "logprobs": state.logprobs,
            "values": state.values,
            "mask": state.mask,
            "rounds": state.rounds,
            "accepted": state.accepted,
            "live_rounds": state.live_rounds,
            "committed": state.committed,
        }

        def body(c):
            cr, k = c
            eff_table = jnp.where(
                cr["done"][:, None], paged.max_blocks, table
            )
            cr = {
                **cr,
                "t_cache": attach_block_table(cr["t_cache"], eff_table),
            }
            cr = spec_round_step(
                cr,
                prompt_mask=state.prompt_mask,
                target_apply=apply_fn,
                target_params=t_params,
                draft_apply=draft_apply,
                draft_params=d_params,
                config=config,
                G=G,
                transition_mask=transition_mask,
                adjust_logits=adjust_logits,
            )
            cr = {**cr, "t_cache": detach_block_table(cr["t_cache"])}
            return cr, k + 1

        def cond(c):
            cr, k = c
            return (k < segment_len) & ~jnp.all(cr["done"])

        final, _ = jax.lax.while_loop(
            cond, body, (carry, jnp.asarray(0, jnp.int32))
        )
        new_state = SpecState(
            tokens=final["tokens"],
            logprobs=final["logprobs"],
            values=final["values"],
            mask=final["mask"],
            prompt_mask=state.prompt_mask,
            cache=PagedKV(final["t_cache"], table),
            d_cache=final["d_cache"],
            t_last=final["t_last"],
            prompt_len=state.prompt_len,
            done=final["done"],
            step=final["n_out"],
            rng=final["rng"],
            rounds=final["rounds"],
            accepted=final["accepted"],
            live_rounds=final["live_rounds"],
            committed=final["committed"],
        )
        return (
            new_state,
            final["live_rounds"] - state.live_rounds,
            final["rounds"] - state.rounds,
        )

    def _decode_segment_paged_kernel(params: Any, state: SlotState):
        """The in-place twin of ``_decode_segment_dense``: same sampling
        and bookkeeping ops on the same values, but the cache threaded
        through ``apply_fn`` is the block pool + (live-masked) table
        instead of a gathered dense view, and sampling runs the fused
        kernel. The per-row sample/bookkeeping stream is bit-identical by
        construction of the two kernels."""
        from trlx_tpu.ops.paged_attention import sample_token_fused

        table = state.cache.block_table

        def step_cache(st: SlotState, live: jax.Array):
            # freeze-mask the table EVERY step: a row that finished mid-
            # segment must stop committing K/V (its blocks may already be
            # recycled after harvest) — out-of-range ids drop all writes
            eff_table = jnp.where(live[:, None], table, paged.max_blocks)
            return attach_block_table(st.cache.pool, eff_table)

        def fold_cache(out_cache: Any) -> PagedKV:
            return PagedKV(detach_block_table(out_cache), table)

        return _segment_loop(
            params, state, step_cache, fold_cache, sample_token_fused
        )

    def _decode_segment_dense(params: Any, state: SlotState):
        return _segment_loop(
            params,
            state,
            lambda st, live: st.cache,
            lambda out_cache: out_cache,
            sample_token_from_logits,
        )

    def _segment_loop(params, state, step_cache, fold_cache, sample_fn):
        def sample_step(carry):
            st, live_steps, k = carry
            new_rng, sample_rng = split_row_keys(st.rng)
            next_token, logprob = sample_fn(
                st.logits, st.step_out, sample_rng, config, st.step, adjust_logits
            )
            live = ~st.done
            next_token = jnp.where(live, next_token, config.pad_token_id).astype(jnp.int32)
            tokens = _row_set(st.tokens, next_token, st.step, live)
            logprobs = _row_set(st.logprobs, jnp.where(live, logprob, 0.0), st.step, live)
            value = st.step_out.get("value", jnp.zeros((B,), jnp.float32))
            values = _row_set(st.values, jnp.where(live, value, 0.0), st.step, live)
            mask = _row_set(st.mask, live.astype(jnp.int32), st.step, live)

            done = st.done
            if config.eos_token_id is not None:
                done = done | (live & (next_token == config.eos_token_id))
            # a live row that just wrote its N-th column is finished even
            # without eos — plain generate's loop exits at step N; here the
            # row must freeze so the next (clamped) write can't clobber its
            # last column while it awaits harvest
            done = done | (live & (st.step + 1 >= N))

            slot = P + st.step  # [B] per-slot cache column
            slot_mask = _row_set(st.slot_mask, live.astype(jnp.int32), slot, live)

            out = apply_fn(
                params,
                next_token[:, None],
                attention_mask=slot_mask,
                positions=(st.prompt_len + st.step)[:, None],
                cache=step_cache(st, live),
                cache_index=slot,
            )
            step_out = {**last_step_info(out), "last_tokens": next_token}
            new_st = SlotState(
                tokens=tokens,
                logprobs=logprobs,
                values=values,
                mask=mask,
                slot_mask=slot_mask,
                # dense view: the forward wrote every row's k/v at its own
                # slot (done rows into dead masked columns — harmless);
                # in-place kernel: only live rows committed, through the
                # live-masked table
                cache=fold_cache(out["cache"]),
                logits=_row_where(live, out["logits"][:, -1, :], st.logits),
                step_out=_row_where(live, step_out, st.step_out),
                prompt_len=st.prompt_len,
                done=done,
                step=jnp.where(live, st.step + 1, st.step),
                rng=_row_where(live, new_rng, st.rng),
            )
            return new_st, live_steps + jnp.sum(live.astype(jnp.int32)), k + 1

        def cond(carry):
            st, _, k = carry
            return (k < segment_len) & ~jnp.all(st.done)

        st, live_steps, steps = jax.lax.while_loop(
            cond, sample_step, (state, jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32))
        )
        return st, live_steps, steps

    if jit:
        decode_segment = jax.jit(decode_segment)
    return SlotRefillFns(
        init_state=empty_spec_state if G else empty_state,
        refill_rows=refill_rows,
        refill_program=refill_program,
        prewarm=prewarm,
        decode_segment=decode_segment,
        batch_size=B,
        prompt_len=P,
        max_new_tokens=N,
        segment_len=segment_len,
        paged=paged,
        decode_kernel=decode_kernel,
        prefill_kernel=prefill_kernel,
        prefill_chunk_rows=prefill_chunk_rows if paged is not None else None,
        prefill_chunk_program=(
            prefill_chunk_program if paged is not None else None
        ),
        speculative=G,
    )
