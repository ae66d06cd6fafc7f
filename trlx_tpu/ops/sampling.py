"""Jitted autoregressive generation with an explicit KV cache.

The reference's dominant hot loop is HF ``generate`` (SURVEY.md §3.2); here it
is one compiled program: a prefill forward that fills the cache for the
(left-padded) prompt block, then a ``lax.while_loop`` decode with per-sample
eos early-exit — static shapes, no host round-trips.

The ``adjust_logits`` hook lets algorithms reshape sampling logits on device —
ILQL's ``logπ + β(minQ − V)`` advantage reshaping plugs in here (reference:
``trlx/models/modeling_ilql.py:280-317``).
"""

import dataclasses
from bisect import bisect_left
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    """Sampling settings (HF-compatible field names, reference
    ``method.gen_kwargs``)."""

    max_new_tokens: int = 40
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    do_sample: bool = True
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0
    min_new_tokens: int = 0
    # Per-row RNG streams: row i samples from its own key chain
    # ``fold_in(rng, i)`` split once per decode step, so a sequence's sampled
    # tokens depend only on (its key, its step) — never on batch composition
    # or slot position. Required by (and implied by) continuous-batching
    # rollouts, where a sequence migrates through refilled cache slots; the
    # default batch-wide stream is kept for byte-for-byte compatibility of
    # existing runs.
    per_row_rng: bool = False

    @staticmethod
    def from_gen_kwargs(kwargs: Dict[str, Any], eos_token_id=None, pad_token_id=0) -> "GenerationConfig":
        known = {f.name for f in dataclasses.fields(GenerationConfig)}
        clean = {k: v for k, v in kwargs.items() if k in known}
        clean.setdefault("eos_token_id", eos_token_id)
        clean.setdefault("pad_token_id", pad_token_id)
        # ILQL passes beta/temperature through gen_kwargs; beta is handled by
        # the adjust_logits hook, so it is not a GenerationConfig field.
        return GenerationConfig(**clean)


def apply_transition_mask(
    mask: jax.Array,  # [Vm, Vm'] bool: allowed next-token per last-token
    last_tokens: jax.Array,  # [B] or [B, T] the conditioning token(s)
    logits: jax.Array,  # [..., V] matching last_tokens' leading dims
) -> jax.Array:
    """Disallow transitions: ``mask[last, next] == False`` → −inf-ish logits.

    Masks smaller than the vocab disallow out-of-range *next* tokens;
    out-of-range *last* tokens (no transition row exists) sample
    unconstrained rather than borrowing an unrelated row's constraints.
    Shared by the step sampler's logit-mask hook and the speculative
    decoder (both must agree exactly for lossless verification).
    """
    last = jnp.clip(last_tokens, 0, mask.shape[0] - 1)
    sel = mask[last]  # [..., mask_vocab]
    V = logits.shape[-1]
    if mask.shape[1] >= V:  # mask over a padded/larger vocab: truncate
        allowed = sel[..., :V]
    else:  # mask narrower than vocab: out-of-range tokens disallowed
        allowed = jnp.zeros(logits.shape, bool)
        allowed = allowed.at[..., : mask.shape[1]].set(sel)
    row_known = (last_tokens >= 0) & (last_tokens < mask.shape[0])
    allowed = allowed | ~row_known[..., None]
    return jnp.where(allowed, logits, -1e10)


def process_logits(
    logits: jax.Array,  # [B, V]
    temperature: float,
    top_k: int,
    top_p: float,
) -> jax.Array:
    """Standard temperature / top-k / top-p filtering (returns logits)."""
    if temperature != 1.0:
        logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k and top_k > 0 and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cumprobs = jnp.cumsum(probs, axis=-1)
        # keep tokens until cumulative prob exceeds top_p (always keep top-1)
        keep_sorted = cumprobs - probs < top_p
        threshold = jnp.min(
            jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < threshold, -jnp.inf, logits)
    return logits




def per_row_keys(rng: jax.Array, batch_size: int) -> jax.Array:
    """Derive ``[B, 2]`` independent per-row key chains from one key.

    Row ``i``'s chain starts at ``fold_in(rng, i)``; every decode step splits
    it once (``split_row_keys``). The single source of truth for BOTH the
    plain sampler's ``per_row_rng`` mode and the continuous-batching engine —
    they must agree exactly for the slot-refill bit-parity guarantee."""
    return jax.vmap(lambda i: jax.random.fold_in(rng, i))(
        jnp.arange(batch_size, dtype=jnp.int32)
    )


def split_row_keys(keys: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One decode step of every row's chain: ``[B, 2]`` keys → (next chain
    keys, this step's sample keys), both ``[B, 2]``."""
    pairs = jax.vmap(lambda k: jax.random.split(k))(keys)  # [B, 2, 2]
    return pairs[:, 0], pairs[:, 1]


def sample_token_from_logits(
    logits: jax.Array,  # [B, V] raw last-position logits
    step_out: Dict[str, Any],
    sample_rng: jax.Array,  # [2] batch-wide key, or [B, 2] per-row keys
    config: GenerationConfig,
    step: jax.Array,  # scalar, or [B] per-slot decode steps
    adjust_logits: Optional[Callable[[Dict[str, Any], jax.Array], jax.Array]],
) -> Tuple[jax.Array, jax.Array]:
    """Shared sampling semantics for every decode loop: adjust-logits hook,
    min_new_tokens eos blocking, temperature/top-k/top-p filtering,
    sample-or-argmax, and behavior logprob of the chosen token.

    ``sample_rng`` may be one batch-wide key (historical behavior) or a
    ``[B, 2]`` stack of per-row keys; ``step`` may be a scalar (all rows in
    lockstep) or a ``[B]`` vector (continuous batching: slots at different
    depths). Per-row sampling is a vmapped categorical, so row ``i``'s token
    depends only on its own key and logits."""
    if adjust_logits is not None:
        logits = adjust_logits(step_out, logits)
    logits = logits.astype(jnp.float32)
    if config.eos_token_id is not None and config.min_new_tokens > 0:
        block_eos = jnp.asarray(step < config.min_new_tokens)
        if block_eos.ndim:  # [B] per-slot steps → broadcast over the vocab
            block_eos = block_eos[:, None]
        logits = jnp.where(
            block_eos
            & (jnp.arange(logits.shape[-1])[None, :] == config.eos_token_id),
            -jnp.inf,
            logits,
        )
    filtered = process_logits(logits, config.temperature, config.top_k, config.top_p)
    if config.do_sample:
        if sample_rng.ndim == 2:  # per-row key chains
            next_token = jax.vmap(
                lambda k, row: jax.random.categorical(k, row)
            )(sample_rng, filtered)
        else:
            next_token = jax.random.categorical(sample_rng, filtered, axis=-1)
    else:
        next_token = jnp.argmax(filtered, axis=-1)
    logprob = jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), next_token[:, None], axis=-1
    )[:, 0]
    return next_token, logprob


_NON_CARRY_KEYS = (
    "cache", "logits", "branch_input", "pre_norm_hidden", "encoder_hidden",
    "router_aux_loss",  # scalar vectors, not [B, ...] — and unused in decode
    "router_load",
    "router_shared",
)


def last_step_info(out: Dict[str, Any]) -> Dict[str, Any]:
    """Keep only last-position views of model outputs so the while_loop
    carry has step-invariant shapes (prefill is [B,P,…], decode [B,1,…])."""
    info = {}
    for k, v in out.items():
        if k in _NON_CARRY_KEYS or v is None:
            continue
        info[k] = jax.tree_util.tree_map(lambda x: x[:, -1], v)
    return info


# The decode loop's cache extents: their step in slots, how many of them a
# layer may have (each is one more attention body a layer to trace, compile
# and load; the step doubles until they fit), and the share of the cache
# reads they must spare to be worth a conditional at all. On one v5e, 128
# prompt + 512 new tokens, 64 rows, medians of six seeds (PERF.md section 6,
# PR 32): a step of 128 slots (four extents) gave the dense and the MoE cell
# +4.6% and +2.2% samples/s, 64 (eight) +6.9% and +3.0%, at warm set-ups of
# 40.7 | 42.2 | 43.5 s and 45.3 | 44.1 | 45.5 s (none | 128 | 64; spread 6 to
# 12%). Constants with their measurement, not settings.
KV_BUCKET = 64
MAX_KV_EXTENTS = 8
MIN_KV_SAVING = 0.1


def kv_extents(prompt_width: int, max_new_tokens: int) -> Tuple[int, ...]:
    """The static cache extents ``generate``'s decode steps attend over: the
    multiples of the bucket strictly between the prompt's width and the
    cache's ``S = P + N`` slots, then ``S``. A step writing slot ``t``
    attends over the first extent of at least ``t + 1``
    (``models/transformer.py::extent_attention``), so a cache that starts a
    fifth full is not read whole at every step. ``(128, 512)`` gives ``(192,
    256, ..., 576, 640)``; ``(128, 1024)`` takes a bucket of 128 to stay at
    eight; a decode that would spare under a tenth of its reads, ``(896,
    128)``, gives ``(1024,)`` and the program that reads every slot."""
    P, N, S = prompt_width, max_new_tokens, prompt_width + max_new_tokens

    def every(bucket):
        return (*range((P // bucket + 1) * bucket, S, bucket), S)

    bucket = KV_BUCKET
    while len(every(bucket)) > MAX_KV_EXTENTS:
        bucket *= 2
    extents = every(bucket)
    if kv_slots_read(extents, P, N) > (1 - MIN_KV_SAVING) * N * S:
        return (S,)
    return extents


def layer_extents(extents: Tuple[int, ...], cache_slots: int) -> Tuple[int, ...]:
    """A layer's own extents: the row's, cut to the slots its cache has. A
    window layer's ring of ``C`` slots (``models/transformer.py::
    make_kv_cache``) is read through ``extents`` while the row is shorter
    than ``C`` and whole, ``C`` slots, from then on."""
    return (*(e for e in extents if e < cache_slots), cache_slots)


def kv_slots_read(extents: Tuple[int, ...], prompt_width: int, steps: int, selected: int = 0) -> int:
    """Cache slots a row's attention reads in one layer over the first
    ``steps`` decode steps under ``extents`` (step ``i`` writes slot ``P +
    i``; a step past the last extent, in a ring, reads the last): host
    arithmetic for ``rollout/kv_read_frac``, against ``steps * S``. Under a
    learned selection of ``selected`` keys a step on a cache of more slots
    reads that many of them, whatever the extent (the indexer's own pass over
    the index keys still follows it)."""
    last = len(extents) - 1
    if selected and extents[last] > selected:
        return steps * selected
    return sum(extents[min(bisect_left(extents, prompt_width + i + 1), last)] for i in range(steps))


class GenerationOutput(NamedTuple):
    sequences: jax.Array  # [B, P + N] prompt (left-padded) ‖ response
    response_tokens: jax.Array  # [B, N] pad-filled after eos
    response_mask: jax.Array  # [B, N] 1 on real response tokens (incl. eos)
    response_logprobs: jax.Array  # [B, N] behavior logprobs of sampled tokens
    response_values: jax.Array  # [B, N] value-head outputs (0 if no head)
    prompt_mask: jax.Array  # [B, P]


def generate(
    apply_fn: Callable[..., Dict[str, Any]],
    params: Any,
    init_cache_fn: Callable[[int, int], Any],
    input_ids: jax.Array,  # [B, P] left-padded prompts
    attention_mask: jax.Array,  # [B, P]
    rng: jax.Array,
    config: GenerationConfig,
    adjust_logits: Optional[Callable[[Dict[str, Any], jax.Array], jax.Array]] = None,
) -> GenerationOutput:
    """Sample ``max_new_tokens`` continuations for a batch of prompts.

    ``apply_fn(params, input_ids, attention_mask, positions, cache,
    cache_index)`` must return a dict with at least ``logits`` and ``cache``
    (the model wrappers' ``__call__``). ``adjust_logits(step_outputs, logits)``
    may reshape the last-token logits before sampling (ILQL). Where the
    decode loop crosses a ``KV_BUCKET`` boundary the single-token step is
    also handed ``kv_extents=`` (:func:`kv_extents`), which the wrappers
    pass down to ``Attention``.

    Fully jittable; wrap in ``jax.jit``/``pjit`` with static ``config``.
    """
    B, P = input_ids.shape
    N = config.max_new_tokens
    S = P + N
    input_ids = input_ids.astype(jnp.int32)

    # single-token steps only; the key is absent where one extent covers the
    # loop, and the step is then the program it was before there were extents
    extents = kv_extents(P, N)
    step_kwargs = {"kv_extents": extents} if len(extents) > 1 else {}

    cache = init_cache_fn(B, S)
    # slot mask over the full cache: prompt mask then zeros (filled as we go)
    slot_mask = jnp.concatenate(
        [attention_mask.astype(jnp.int32), jnp.zeros((B, N), jnp.int32)], axis=1
    )

    # ---- prefill ----
    # only the last position's logits seed the sampler: restrict the vocab
    # projection to it (the full-span projection is the prefill's biggest op)
    prefill_out = apply_fn(
        params,
        input_ids,
        attention_mask=slot_mask,
        positions=None,
        cache=cache,
        cache_index=jnp.asarray(0, jnp.int32),
        logits_span=(P - 1, P),
    )
    cache = prefill_out["cache"]
    last_logits = prefill_out["logits"][:, -1, :]  # [B, V]
    prompt_len = jnp.sum(attention_mask, axis=1).astype(jnp.int32)  # [B]

    class Carry(NamedTuple):
        tokens: jax.Array  # [B, N]
        logprobs: jax.Array  # [B, N]
        values: jax.Array  # [B, N]
        mask: jax.Array  # [B, N]
        slot_mask: jax.Array  # [B, S]
        cache: Any
        logits: jax.Array  # [B, V] logits for the next sample
        step_out: Any  # last-position views of last forward (for adjust_logits)
        done: jax.Array  # [B]
        step: jax.Array  # scalar
        rng: jax.Array

    def sample_step(carry: Carry) -> Carry:
        if config.per_row_rng:
            rng, sample_rng = split_row_keys(carry.rng)
        else:
            rng, sample_rng = jax.random.split(carry.rng)
        next_token, logprob = sample_token_from_logits(
            carry.logits, carry.step_out, sample_rng, config, carry.step, adjust_logits
        )

        next_token = jnp.where(carry.done, config.pad_token_id, next_token).astype(jnp.int32)
        live = ~carry.done
        tokens = carry.tokens.at[:, carry.step].set(next_token)
        logprobs = carry.logprobs.at[:, carry.step].set(jnp.where(live, logprob, 0.0))
        values = carry.values.at[:, carry.step].set(
            jnp.where(live, carry_step_value(carry), 0.0)
        )
        mask = carry.mask.at[:, carry.step].set(live.astype(jnp.int32))

        done = carry.done
        if config.eos_token_id is not None:
            done = done | (next_token == config.eos_token_id)

        # write slot mask for this token (live samples only)
        slot = P + carry.step
        slot_mask = carry.slot_mask.at[:, slot].set(live.astype(jnp.int32))

        # forward one step
        out = apply_fn(
            params,
            next_token[:, None],
            attention_mask=slot_mask,
            positions=(prompt_len + carry.step)[:, None],
            cache=carry.cache,
            cache_index=slot,
            **step_kwargs,
        )
        return Carry(
            tokens=tokens,
            logprobs=logprobs,
            values=values,
            mask=mask,
            slot_mask=slot_mask,
            cache=out["cache"],
            logits=out["logits"][:, -1, :],
            step_out={**last_step_info(out), "last_tokens": next_token},
            done=done,
            step=carry.step + 1,
            rng=rng,
        )

    def carry_step_value(carry: Carry) -> jax.Array:
        # value prediction for the *state before* sampling this token
        if "value" in carry.step_out:
            return carry.step_out["value"]
        return jnp.zeros((B,), jnp.float32)

    def cond(carry: Carry) -> jax.Array:
        return (carry.step < N) & ~jnp.all(carry.done)

    init = Carry(
        tokens=jnp.full((B, N), config.pad_token_id, jnp.int32),
        logprobs=jnp.zeros((B, N), jnp.float32),
        values=jnp.zeros((B, N), jnp.float32),
        mask=jnp.zeros((B, N), jnp.int32),
        slot_mask=slot_mask,
        cache=cache,
        logits=last_logits,
        step_out={**last_step_info(prefill_out), "last_tokens": input_ids[:, -1]},
        done=jnp.zeros((B,), bool),
        step=jnp.asarray(0, jnp.int32),
        rng=per_row_keys(rng, B) if config.per_row_rng else rng,
    )
    final = jax.lax.while_loop(cond, sample_step, init)

    sequences = jnp.concatenate([input_ids, final.tokens], axis=1)
    return GenerationOutput(
        sequences=sequences,
        response_tokens=final.tokens,
        response_mask=final.mask,
        response_logprobs=final.logprobs,
        response_values=final.values,
        prompt_mask=attention_mask.astype(jnp.int32),
    )


def generate_seq2seq(
    encode_fn: Callable[..., Tuple[jax.Array, Any]],
    decode_fn: Callable[..., Dict[str, Any]],
    params: Any,
    input_ids: jax.Array,  # [B, P] right-padded encoder prompts
    attention_mask: jax.Array,  # [B, P]
    rng: jax.Array,
    config: GenerationConfig,
    start_token_id: int = 0,
    adjust_logits: Optional[Callable[[Dict[str, Any], jax.Array], jax.Array]] = None,
) -> GenerationOutput:
    """Seq2seq sampling: one encoder pass, then a ``lax.while_loop`` decoder
    (reference: HF ``generate`` on the T5 wrappers, used by the seq2seq PPO/
    ILQL paths ``trlx/trainer/accelerate_ppo_trainer.py:152-179``,
    ``modeling_ilql.py:460-488``).

    ``encode_fn(params, input_ids, attention_mask, max_decode_len)`` returns
    ``(encoder_hidden, decoder_cache)`` with cross-attn K/V prefilled;
    ``decode_fn(params, decoder_input_ids, encoder_hidden, encoder_mask,
    cache, cache_index)`` returns at least ``logits`` and ``cache``.

    Decoder sequences all start at slot 0 with ``start_token_id`` — no
    left-padding complications. Fully jittable with static ``config``.
    """
    B, P = input_ids.shape
    N = config.max_new_tokens
    input_ids = input_ids.astype(jnp.int32)

    enc_hidden, cache = encode_fn(params, input_ids, attention_mask, N + 1)
    start = jnp.full((B, 1), start_token_id, jnp.int32)
    out0 = decode_fn(
        params, start, enc_hidden, attention_mask, cache, jnp.asarray(0, jnp.int32)
    )

    class Carry(NamedTuple):
        tokens: jax.Array
        logprobs: jax.Array
        values: jax.Array
        mask: jax.Array
        cache: Any
        logits: jax.Array
        step_out: Any
        done: jax.Array
        step: jax.Array
        rng: jax.Array

    def sample_step(carry: Carry) -> Carry:
        if config.per_row_rng:
            rng, sample_rng = split_row_keys(carry.rng)
        else:
            rng, sample_rng = jax.random.split(carry.rng)
        next_token, logprob = sample_token_from_logits(
            carry.logits, carry.step_out, sample_rng, config, carry.step, adjust_logits
        )

        next_token = jnp.where(carry.done, config.pad_token_id, next_token).astype(jnp.int32)
        live = ~carry.done
        tokens = carry.tokens.at[:, carry.step].set(next_token)
        logprobs = carry.logprobs.at[:, carry.step].set(jnp.where(live, logprob, 0.0))
        value = carry.step_out.get("value", jnp.zeros((B,), jnp.float32))
        values = carry.values.at[:, carry.step].set(jnp.where(live, value, 0.0))
        mask = carry.mask.at[:, carry.step].set(live.astype(jnp.int32))

        done = carry.done
        if config.eos_token_id is not None:
            done = done | (next_token == config.eos_token_id)

        out = decode_fn(
            params, next_token[:, None], enc_hidden, attention_mask,
            carry.cache, carry.step + 1,
        )
        return Carry(
            tokens=tokens,
            logprobs=logprobs,
            values=values,
            mask=mask,
            cache=out["cache"],
            logits=out["logits"][:, -1, :],
            step_out={**last_step_info(out), "last_tokens": next_token},
            done=done,
            step=carry.step + 1,
            rng=rng,
        )

    def cond(carry: Carry) -> jax.Array:
        return (carry.step < N) & ~jnp.all(carry.done)

    init = Carry(
        tokens=jnp.full((B, N), config.pad_token_id, jnp.int32),
        logprobs=jnp.zeros((B, N), jnp.float32),
        values=jnp.zeros((B, N), jnp.float32),
        mask=jnp.zeros((B, N), jnp.int32),
        cache=out0["cache"],
        logits=out0["logits"][:, -1, :],
        step_out={**last_step_info(out0), "last_tokens": start[:, 0]},
        done=jnp.zeros((B,), bool),
        step=jnp.asarray(0, jnp.int32),
        rng=per_row_keys(rng, B) if config.per_row_rng else rng,
    )
    final = jax.lax.while_loop(cond, sample_step, init)

    sequences = jnp.concatenate([input_ids, final.tokens], axis=1)
    return GenerationOutput(
        sequences=sequences,
        response_tokens=final.tokens,
        response_mask=final.mask,
        response_logprobs=final.logprobs,
        response_values=final.values,
        prompt_mask=attention_mask.astype(jnp.int32),
    )
