"""Speculative decoding for rollout generation (draft-and-verify).

Beyond the reference (whose generation hot loop is plain HF ``generate``,
SURVEY.md §3.2): a drafter proposes ``gamma`` tokens, the target model
scores all of them in ONE forward, and a rejection-sampling acceptance rule
keeps a prefix — provably sampling from the target distribution (Leviathan
et al. 2023; Chen et al. 2023). Per round the target runs one
length-``gamma+1`` forward instead of up to ``gamma+1`` single-token
decodes, so rollout wall-clock approaches the draft's cost when the draft
approximates the target well.

Two drafters, one round (``Drafter``; ``spec_round_step`` is the verify, the
acceptance rule, the residual and bonus draws and the per-row bookkeeping
for both):

- ``model_drafter``: a SEPARATE small model (``model.draft_model_path``)
  with a full cache of its own, ``gamma`` single-token forwards a round;
- ``module_drafter``: the target's OWN next-token-prediction module
  (``TransformerConfig.mtp_layers``, ``CausalTransformer.draft``; K-EXAONE
  publishes one), one proposal a round from the target's last hidden states,
  the target's embedding and head, and one cache layer of its own. The
  model's published key decides (``trainer/base.py``), no option does. The
  target's verify is then ONE forward over two tokens a row at the row's own
  cache index; a window layer's cache is a ring of ``window + gamma`` slots
  that takes that span at a per-row index (``CausalTransformer._ring_plan``),
  so the rows may be longer than the window.

TPU-first structure: the whole sampler is one jitted program — a
``lax.while_loop`` over rounds with static shapes throughout. Rows accept
different prefix lengths, so both KV caches use per-row write indices (the
``[B]``-vector ``cache_index`` path of ``models/transformer.py::Attention``)
and committed-token bookkeeping is per row. A round writes at those per-row
offsets without a loop over the rows: its block of ``gamma + 1`` entries goes
into each ``[B, N + gamma + 1]`` output buffer as a blend over the buffer, and
its K/V go into a dense cache as one scatter of ``(row, slot)`` index pairs,
because a vmapped ``dynamic_update_slice`` becomes a scatter batched over the
rows, which the chip runs one row at a time. Rounds are stateless: each
starts by re-feeding the last committed token (whose K/V the caches lack —
it was sampled from a residual/bonus distribution, never forwarded), which
also re-derives both models' next-token distributions, so no logits are
carried across rounds and cache rewinds are just index arithmetic.

Exactness properties (tested in ``tests/test_speculative.py``):

- greedy (``do_sample=False``) output is bit-identical to the plain
  sampler's greedy output, for ANY draft;
- with draft == target every proposal is accepted (acceptance ratio 1);
- returned logprobs/values are the TARGET's, with the same semantics as
  :func:`trlx_tpu.ops.sampling.generate` (behavior logprob of the chosen
  token under the unfiltered target distribution; value of the state the
  token was sampled from), so PPO's ``make_experience`` is agnostic to
  which sampler produced the rollout;
- ``per_row_rng=True`` threads [B, 2] per-row key chains through every
  draw site (draft proposals, acceptance uniforms, residual/bonus), so a
  batched run is BIT-IDENTICAL per row to running each row alone with its
  chain — batch composition invariance, the property continuous batching
  needs to host a speculative slot. The per-row sampled streams differ
  from the batch-wide mode's by construction; both are exact draws from the
  target distribution.

Transition logit masks (the trainer's ``logit_mask``, e.g. randomwalks'
allowed-moves table) compose natively: the mask is applied to the draft AND
the target distributions, so constrained sampling stays lossless. So does
``min_new_tokens``: eos is blocked per ROW at response positions below the
minimum — on the draft proposals and on the target's verify distributions
alike, before both sampling and the behavior logprob — exactly the plain
sampler's semantics. And so does the full ``adjust_logits`` hook (ILQL's
Q-value reshaping): it is applied to the target's per-position verify
outputs — sampling is exact w.r.t. the ADJUSTED target distribution, and
the (unadjusted) draft's mismatch only costs acceptance rate.
"""

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from trlx_tpu.ops import cache_layout
from trlx_tpu.ops.sampling import (
    _NON_CARRY_KEYS,
    GenerationConfig,
    GenerationOutput,
    apply_transition_mask,
    per_row_keys,
    process_logits,
    split_row_keys,
)


def _filtered_probs(logits: jax.Array, config: GenerationConfig) -> jax.Array:
    """The actual sampling distribution: temperature/top-k/top-p filtered
    softmax (matches ``sample_token_from_logits``'s sampling path)."""
    return jax.nn.softmax(
        process_logits(logits, config.temperature, config.top_k, config.top_p),
        axis=-1,
    )


def accept_and_extra(
    p_probs: jax.Array,  # [B, G+1, V] target dists p_0..p_G
    q_probs: jax.Array,  # [B, G, V] draft dists q_1..q_G
    d_toks: jax.Array,  # [B, G] draft proposals (d_i ~ q_i)
    rng: jax.Array,
    do_sample: bool,
):
    """The speculative acceptance rule as a pure function of distributions.

    Returns ``(k, extra_tok, rng)``: ``k`` accepted draft tokens (the
    committed block is ``d_1..d_k, extra``), the residual/bonus ``extra``
    token, and the advanced rng (callers must thread it — reusing the input
    rng would correlate later draws with the acceptance draws).
    Sampling: accept ``d_i`` iff ``u·q_i(d_i) < p_{i-1}(d_i)``; on the first
    rejection resample from ``norm(max(p−q, 0))``; after a full accept,
    sample the bonus from ``p_G``. This is the Leviathan/Chen rejection
    scheme — the marginal of every committed token is EXACTLY the target's
    (machine-checked against enumerated distributions in
    ``tests/test_speculative.py::test_acceptance_rule_is_distribution_exact``).
    Greedy: accept iff ``d_i == argmax p_{i-1}``; extra = ``argmax p_k``.

    ``rng`` may be one batch-wide key (``[2]``, historical behavior) or a
    ``[B, 2]`` stack of per-row key chains (``per_row_rng``): each row then
    draws its acceptance uniforms and residual/bonus token from its OWN
    chain — one ``split_row_keys`` advance per draw site, so a row's
    stream depends only on (its chain, its round), never on batch
    composition. That is what makes a batched per-row run bit-identical to
    running each row alone (the B=1-loop parity test).
    """
    B, G = d_toks.shape
    per_row = rng.ndim == 2
    q_sel = jnp.take_along_axis(q_probs, d_toks[..., None], axis=-1)[..., 0]
    p_sel = jnp.take_along_axis(
        p_probs[:, :G, :], d_toks[..., None], axis=-1
    )[..., 0]  # p_{i-1}(d_i)
    if do_sample:
        if per_row:
            rng, ru = split_row_keys(rng)
            u = jax.vmap(lambda kk: jax.random.uniform(kk, (G,)))(ru)
        else:
            rng, ru = jax.random.split(rng)
            u = jax.random.uniform(ru, (B, G))
        # strict <: u ∈ [0,1) can be exactly 0, and `0·q <= 0` would accept
        # a token with ZERO target probability. Accept iff u < p/q.
        accept = u * q_sel < p_sel
    else:
        accept = d_toks == jnp.argmax(p_probs[:, :G, :], axis=-1)
    k = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)

    p_row_at_k = jnp.take_along_axis(p_probs, k[:, None, None], axis=1)[:, 0, :]
    if do_sample:
        res_probs = jnp.maximum(p_probs[:, :G, :] - q_probs, 0.0)  # [B, G, V]
        res_at_k = jnp.take_along_axis(
            res_probs, jnp.minimum(k, G - 1)[:, None, None], axis=1
        )[:, 0, :]
        res_sum = jnp.sum(res_at_k, axis=-1, keepdims=True)
        # bonus (k == G) samples p_G; degenerate residual (p == q exactly)
        # also falls back to p — both are distribution-exact
        extra_dist = jnp.where(
            (k[:, None] < G) & (res_sum > 1e-20),
            res_at_k / jnp.maximum(res_sum, 1e-20),
            p_row_at_k,
        )
        extra_logits = jnp.log(jnp.maximum(extra_dist, 1e-30))
        if per_row:
            rng, re = split_row_keys(rng)
            extra_tok = jax.vmap(
                lambda kk, row: jax.random.categorical(kk, row)
            )(re, extra_logits).astype(jnp.int32)
        else:
            rng, re = jax.random.split(rng)
            extra_tok = jax.random.categorical(
                re, extra_logits, axis=-1
            ).astype(jnp.int32)
    else:
        # greedy: the target would deterministically pick argmax p_k
        extra_tok = jnp.argmax(p_row_at_k, axis=-1).astype(jnp.int32)
    return k, extra_tok, rng


class Drafter(NamedTuple):
    """What differs between the drafters of a speculative round: everything
    else (the verify, ``accept_and_extra``, the residual and bonus draws, the
    per-row bookkeeping, ``min_new_tokens``, the transition mask,
    ``adjust_logits``) is ``spec_round_step``'s. ``state`` is the drafter's
    part of the carry (``carry["d_cache"]``).

    - ``prefill(params, target_prefill_out, input_ids, slot_mask, cache)``:
      the state before the first round;
    - ``propose(params, state, draw, rng, t_last, c, mask_round)``: ``G``
      proposals ``[B, G]``, the distributions they were drawn from ``[B, G,
      V]`` float32, the advanced rng and the state; ``draw(logits, prev, j,
      rng)`` is the round's own ``(token, probs, rng)``;
    - ``settle(state, t_out, block_toks, commit_len)``: the state once the
      round has committed ``commit_len`` of ``block_toks``."""

    prefill: Callable[..., Any]
    propose: Callable[..., Any]
    settle: Callable[..., Any]


def model_drafter(draft_apply: Callable[..., Any], G: int) -> Drafter:
    """A SEPARATE small model with a cache of its own: ``G`` single-token
    forwards a round (unrolled: ``G`` is small and static), rewound by index
    arithmetic alone."""

    def prefill(params, t_pre, input_ids, slot0, cache):
        P = input_ids.shape[1]
        return draft_apply(
            params, input_ids, attention_mask=slot0, positions=None,
            cache=cache, cache_index=jnp.asarray(0, jnp.int32), logits_span=(P - 1, P),
        )["cache"]

    def propose(params, d_cache_r, draw, rng, t_last, c, mask_round):
        tok_r = t_last
        d_toks, q_probs = [], []
        for j in range(G):
            prev = tok_r  # the token being fed — q_{j+1} conditions on it
            out_j = draft_apply(
                params, tok_r[:, None], attention_mask=mask_round,
                positions=None, cache=d_cache_r, cache_index=c - 1 + j,
            )
            tok_r, probs_j, rng = draw(out_j["logits"][:, -1, :], prev, j, rng)
            d_toks.append(tok_r)
            q_probs.append(probs_j)
            d_cache_r = out_j["cache"]
        # one more draft forward to write d_G's K/V (logits discarded):
        # after a fully-accepted round the NEXT round marks d_G's slot
        # committed, and a zero-K/V hole there would quietly degrade every
        # subsequent proposal — exactly in the high-acceptance regime
        d_cache_new = draft_apply(
            params, tok_r[:, None], attention_mask=mask_round,
            positions=None, cache=d_cache_r, cache_index=c - 1 + G,
            logits_span=(0, 0),
        )["cache"]
        return jnp.stack(d_toks, axis=1), jnp.stack(q_probs, axis=1), rng, d_cache_new

    return Drafter(prefill, propose, lambda state, t_out, block_toks, commit_len: state)


def module_drafter(draft_apply: Callable[..., Any]) -> Drafter:
    """The TARGET's own next-token-prediction module
    (``CausalTransformer.draft``: ``draft_apply(params, hidden, next_ids,
    attention_mask=, cache=, cache_index=, logits_span=)``), one proposal a
    round: no second model and no second full cache, the target's embedding
    and head, and the target's last hidden states in place of a forward of
    its own stack.

    The module's entry for slot ``s`` is made from ``(h_s, x_{s+1})``, the
    target's pre-norm hidden state at ``s`` and the token after it, and gives
    a distribution over ``x_{s+2}``. With ``t_last = x_{c-1}`` at slot ``c -
    1`` still to be forwarded, the proposal for slot ``c`` comes from entry
    ``c - 2``. A round committed one or two tokens, so entry ``c - 3`` may be
    new as well: the state carries the last TWO pairs ``(h, next token)``, at
    slots ``c - 3`` and ``c - 2``, the module runs ONE forward over both at
    the row's own index (writing an entry a second time writes the same
    numbers), and ``settle`` slides the pairs by the tokens committed, over
    the two hidden states the verify just made (slots ``c - 1`` and ``c``)."""

    def prefill(params, t_pre, input_ids, slot0, cache):
        P = input_ids.shape[1]
        if P < 3:
            raise ValueError(f"a self-drafting model needs prompts of at least 3 slots (padded), got {P}")
        hidden = t_pre["pre_norm_hidden"]  # [B, P, E]
        # entry s from (h_s, x_{s+1}); the last has no next token yet and is written again, with
        # the first committed token, before any query reads it (slot-causality hides it until then)
        next_ids = jnp.concatenate([input_ids[:, 1:], input_ids[:, -1:]], axis=1)
        d_cache = draft_apply(
            params, hidden, next_ids, attention_mask=slot0, cache=cache,
            cache_index=jnp.asarray(0, jnp.int32), logits_span=(0, 0),
        )["cache"]
        return {"cache": d_cache, "hidden": hidden[:, P - 3 : P - 1], "next": input_ids[:, P - 2 :]}

    def propose(params, state, draw, rng, t_last, c, mask_round):
        out = draft_apply(
            params, state["hidden"], state["next"], attention_mask=mask_round,
            cache=state["cache"], cache_index=c - 3, logits_span=(1, 2),
        )
        tok, probs, rng = draw(out["logits"][:, -1, :], t_last, 0, rng)
        return tok[:, None], probs[:, None, :], rng, {**state, "cache": out["cache"]}

    def settle(state, t_out, block_toks, commit_len):
        def slide(old, new):  # rows [B, 2 + 2, ...] of slots c - 3 .. c, from the row's commit_len on
            both = jnp.concatenate([old, new.astype(old.dtype)], axis=1)
            return jax.vmap(lambda row, at: jax.lax.dynamic_slice_in_dim(row, at, 2, axis=0))(both, commit_len)

        return {
            "cache": state["cache"],
            "hidden": slide(state["hidden"], t_out["pre_norm_hidden"]),
            "next": slide(state["next"], block_toks),
        }

    return Drafter(prefill, propose, settle)


def write_row_blocks(buf: jax.Array, blk: jax.Array, off: jax.Array) -> jax.Array:
    """``buf[b, off[b] : off[b] + W] = blk[b]`` for every row ``b`` of ``buf
    [B, NB]``, ``blk [B, W]`` and ``off [B]`` (``0 <= off <= NB - W``), as a
    blend over the buffer: column ``n`` of row ``b`` takes entry ``n -
    off[b]`` of the row's block where there is one. ``W`` selects that fuse
    into one element-wise pass over the buffer; no gather (a ``take_along_axis``
    here is one, of single elements) and nothing serial in the rows."""
    rel = jnp.arange(buf.shape[1])[None, :] - off[:, None]  # [B, NB]
    for j in range(blk.shape[1]):
        buf = jnp.where(rel == j, blk[:, j : j + 1].astype(buf.dtype), buf)
    return buf


def spec_round_step(
    carry: dict,
    *,
    prompt_mask: jax.Array,  # [B, P] int32
    target_apply: Callable[..., Any],
    target_params: Any,
    draft_apply: Optional[Callable[..., Any]] = None,
    draft_params: Any,
    config: GenerationConfig,
    G: int,
    transition_mask: Optional[jax.Array] = None,
    adjust_logits: Optional[Callable[[Any, jax.Array], jax.Array]] = None,
    drafter: Optional[Drafter] = None,  # None: a separate model behind `draft_apply`
) -> dict:
    """One draft-propose → verify → accept round over the shared carry.

    THE speculative round: both ``generate_speculative``'s while_loop body
    and the continuous-batching spec segment's round body
    (``ops/slot_refill.py``) are this one function, so a slot's token
    stream is bit-identical to a solo run by construction rather than by
    mirrored code. The contract that makes that hold across refills and
    batch composition:

    - caches must span ``S = P + N + G`` slots (the solo width — masked
      columns contribute exact-0.0 softmax, but a narrower key axis
      changes the dots' lowering, see ``_make_prefill_chunk``);
    - every forward masks exactly committed slots + the round's ``G``
      probe slots ``[c, c+G)`` — slot-causality inside the model keeps
      everything else (stale pool values included) invisible;
    - the rng chain advances a FIXED number of ``split_row_keys`` draws
      per round (G proposal draws + 2 acceptance draws when sampling),
      so a row's stream depends only on (its chain, its round index).

    Carry keys: ``rng`` ([B,2] per-row chains or [2] batch-wide), ``n_out``
    [B] committed generated tokens, ``done`` [B], ``t_last`` [B] (last
    committed token — its K/V is re-derived by re-feeding, never carried),
    ``t_cache``/``d_cache``, output buffers ``tokens``/``logprobs``/
    ``values``/``mask`` [B, N+G+1], and the scalar counters ``rounds``/
    ``accepted``/``live_rounds``/``committed``.
    """
    B, P = prompt_mask.shape
    N = config.max_new_tokens
    NB = N + G + 1
    V_pad = config.pad_token_id
    per_row = jnp.asarray(carry["rng"]).ndim == 2

    rng = carry["rng"]
    n_out = carry["n_out"]  # [B] committed generated tokens
    done = carry["done"]
    t_last = carry["t_last"]  # [B] last committed token (slot c-1)
    c = P + n_out  # [B] next free slot per row

    # slot mask for this round's forwards: committed slots + the G
    # proposal slots [c, c+G) — slot-causality inside the models keeps
    # stale/future slots invisible to each query
    gen_slots = jnp.arange(NB - 1)[None, :]
    committed = jnp.concatenate(
        [prompt_mask, (gen_slots < n_out[:, None]).astype(jnp.int32)], axis=1
    )
    probe = (gen_slots >= n_out[:, None]) & (gen_slots < (n_out + G)[:, None])
    mask_round = committed + jnp.concatenate(
        [jnp.zeros((B, P), jnp.int32), probe.astype(jnp.int32)], axis=1
    )

    # ---- the drafter proposes G tokens ----
    def draw(logits_j, prev, j, rng):
        """Proposal ``j`` of the round from the drafter's logits: ``(token,
        the float32 distribution it was drawn from, rng)``. The rejection-
        sampling identity needs the SAME q as the accept test (a rounded copy
        would sample the extra token from rounding noise when p ≈ q,
        precisely the good-draft case)."""
        logits_j = logits_j.astype(jnp.float32)
        if transition_mask is not None:
            logits_j = apply_transition_mask(transition_mask, prev, logits_j)
        if config.eos_token_id is not None and config.min_new_tokens > 0:
            # proposal j lands at response position n_out + j: block eos
            # there exactly like the plain sampler (q then matches the
            # distribution the proposal is actually drawn from)
            block_j = (n_out + j) < config.min_new_tokens  # [B]
            logits_j = jnp.where(
                block_j[:, None]
                & (jnp.arange(logits_j.shape[-1])[None, :] == config.eos_token_id),
                -jnp.inf,
                logits_j,
            )
        probs_j = _filtered_probs(logits_j, config)
        if per_row:
            rng, rj = split_row_keys(rng)
        else:
            rng, rj = jax.random.split(rng)
        if config.do_sample:
            log_probs_j = jnp.log(jnp.maximum(probs_j, 1e-30))
            if per_row:
                tok = jax.vmap(
                    lambda kk, row: jax.random.categorical(kk, row)
                )(rj, log_probs_j).astype(jnp.int32)
            else:
                tok = jax.random.categorical(
                    rj, log_probs_j, axis=-1
                ).astype(jnp.int32)
        else:
            tok = jnp.argmax(probs_j, axis=-1).astype(jnp.int32)
        return tok, probs_j, rng

    if drafter is None:
        drafter = model_drafter(draft_apply, G)
    with jax.named_scope("trlx/spec_draft"):
        d_toks, q_probs, rng, d_state = drafter.propose(
            draft_params, carry["d_cache"], draw, rng, t_last, c, mask_round
        )

    # ---- one target forward verifies everything ----
    verify_in = jnp.concatenate([t_last[:, None], d_toks], axis=1)  # [B, G+1]
    with jax.named_scope("trlx/spec_verify"):
        t_out = target_apply(
            target_params, verify_in, attention_mask=mask_round,
            positions=None, cache=carry["t_cache"], cache_index=c - 1,
        )
    t_cache_new = t_out["cache"]
    t_logits = t_out["logits"].astype(jnp.float32)  # [B, G+1, V]
    if adjust_logits is not None:
        # same order as the plain sampler: algo reshaping first, then
        # transition mask, then min_new_tokens eos blocking. step_info
        # mirrors the plain sampler's step_out keys (incl. last_tokens),
        # but fields keep the verify shape [B, G+1, ...] where plain
        # passes last-position [B, ...] views — hence the hook contract:
        # leading-dim polymorphic (see BaseRLTrainer.adjust_logits_fn)
        step_info = {
            k: v for k, v in t_out.items()
            if k not in _NON_CARRY_KEYS and v is not None
        }
        step_info["last_tokens"] = verify_in  # token position j conditions on
        t_logits = adjust_logits(step_info, t_logits)
    if transition_mask is not None:
        # p_j conditions on verify position j's input token — identical
        # masking to the plain sampler's logit-mask hook, so behavior
        # logprobs below come from the same (masked) distribution
        t_logits = apply_transition_mask(transition_mask, verify_in, t_logits)
    if config.eos_token_id is not None and config.min_new_tokens > 0:
        # verify position j produces response position n_out + j; the
        # plain sampler blocks eos there BEFORE both sampling and the
        # behavior logprob, so the mask goes on t_logits (feeding
        # p_probs and t_logprobs_all alike) for exactness
        pos = n_out[:, None] + jnp.arange(G + 1)[None, :]  # [B, G+1]
        t_logits = jnp.where(
            (pos < config.min_new_tokens)[..., None]
            & (
                jnp.arange(t_logits.shape[-1])[None, None, :]
                == config.eos_token_id
            ),
            -jnp.inf,
            t_logits,
        )
    p_probs = _filtered_probs(t_logits, config)  # p_0 .. p_G
    t_logprobs_all = jax.nn.log_softmax(t_logits, axis=-1)
    t_values = t_out.get("value")
    if t_values is None:
        t_values = jnp.zeros(verify_in.shape, jnp.float32)
    t_values = t_values.astype(jnp.float32)  # [B, G+1]

    # ---- acceptance (the pure rejection-sampling rule) ----
    k, extra_tok, rng = accept_and_extra(
        p_probs, q_probs, d_toks, rng, config.do_sample
    )

    # ---- tentative committed block: d_1..d_k, extra ----
    j_iota = jnp.arange(G + 1)[None, :]
    block_toks = jnp.concatenate([d_toks, jnp.zeros((B, 1), jnp.int32)], axis=1)
    block_toks = jnp.where(j_iota == k[:, None], extra_tok[:, None], block_toks)
    block_lp = jnp.take_along_axis(
        t_logprobs_all, block_toks[..., None], axis=-1
    )[..., 0]  # log p_j(x_j) — target logprob of each committed token
    block_val = t_values  # v before sampling x_j is at index j

    valid = j_iota <= k[:, None]
    # respect the N budget and prior completion
    valid = valid & ((n_out[:, None] + j_iota) < N) & (~done[:, None])
    if config.eos_token_id is not None:
        is_eos = block_toks == config.eos_token_id
        eos_before = jnp.cumsum(
            jnp.pad(is_eos.astype(jnp.int32), ((0, 0), (1, 0)))[:, :-1], axis=1
        )
        valid = valid & (eos_before == 0)
    commit_len = jnp.sum(valid.astype(jnp.int32), axis=1)  # [B]
    block_toks_w = jnp.where(valid, block_toks, V_pad)
    block_lp_w = jnp.where(valid, block_lp, 0.0)
    block_val_w = jnp.where(valid, block_val, 0.0)
    block_mask_w = valid.astype(jnp.int32)

    # ---- per-row block write into the output buffers ----
    # never write past the buffer; done rows re-write pads over pads
    off = jnp.minimum(n_out, NB - (G + 1))
    tokens = write_row_blocks(carry["tokens"], block_toks_w, off)
    logprobs = write_row_blocks(carry["logprobs"], block_lp_w, off)
    values = write_row_blocks(carry["values"], block_val_w, off)
    out_mask = write_row_blocks(carry["mask"], block_mask_w, off)

    n_new = n_out + commit_len
    done_new = done | (n_new >= N)
    if config.eos_token_id is not None:
        done_new = done_new | jnp.any(
            (block_toks_w == config.eos_token_id) & (valid), axis=1
        )
    last_idx = jnp.maximum(commit_len - 1, 0)
    t_last_new = jnp.where(
        commit_len > 0,
        jnp.take_along_axis(block_toks_w, last_idx[:, None], axis=1)[:, 0],
        t_last,
    )

    return {
        "rng": rng,
        "n_out": n_new,
        "done": done_new,
        "t_last": t_last_new,
        "t_cache": t_cache_new,
        "d_cache": drafter.settle(d_state, t_out, block_toks_w, commit_len),
        "tokens": tokens,
        "logprobs": logprobs,
        "values": values,
        "mask": out_mask,
        "rounds": carry["rounds"] + 1,
        # accepted draft tokens this round, live rows only — k is
        # PRE-truncation acceptance (budget/eos clipping is not
        # rejection), so the rate reflects draft quality alone
        "accepted": carry["accepted"] + jnp.sum(jnp.where(~done, k, 0)),
        "live_rounds": carry["live_rounds"] + jnp.sum((~done).astype(jnp.int32)),
        # tokens actually committed (post budget/eos truncation) — the
        # tokens-per-round throughput numerator
        "committed": carry["committed"] + jnp.sum(jnp.where(~done, commit_len, 0)),
    }


def generate_speculative(
    target_apply: Callable[..., Any],
    target_params: Any,
    draft_apply: Callable[..., Any],
    draft_params: Any,
    init_target_cache: Callable[[int, int], Any],
    init_draft_cache: Callable[[int, int], Any],
    input_ids: jax.Array,  # [B, P] left-padded prompts
    attention_mask: jax.Array,  # [B, P]
    rng: jax.Array,
    config: GenerationConfig,
    gamma: int = 4,
    return_stats: bool = False,
    transition_mask: Optional[jax.Array] = None,  # [Vm, Vm'] bool: the
    # trainer's prev→next logit mask; applied identically to draft AND
    # target so constrained sampling (e.g. randomwalks) stays lossless
    drafter: Optional[Drafter] = None,  # None: a separate model behind
    # ``draft_apply`` (``model_drafter``); ``module_drafter(...)``: the
    # target's own next-token-prediction module, ``gamma`` its one proposal
    adjust_logits: Optional[Callable[[Any, jax.Array], jax.Array]] = None,
    # algorithm logit reshaping (ILQL: log π + β(minQ − V)) applied to the
    # TARGET's verify distributions — step_out carries the target forward's
    # per-position outputs ([B, G+1, ...] views), so the hook must be
    # shape-polymorphic over leading dims (the trainer's hooks are). The
    # draft proposes from its own unadjusted distribution; the acceptance
    # rule corrects it, so sampling stays exact w.r.t. the ADJUSTED target
    # — a mismatched draft just lowers the acceptance rate.
):
    """Sample ``config.max_new_tokens`` continuations via draft-and-verify.

    ``*_apply(params, input_ids, attention_mask, positions, cache,
    cache_index, **kw)`` follow the model wrappers' ``__call__`` contract;
    the target's outputs must include ``logits`` (+ ``value`` when a value
    head is attached), the draft's just ``logits``. Fully jittable with
    static ``config``/``gamma``.
    """
    B, P = input_ids.shape
    per_row = bool(config.per_row_rng)
    if per_row:
        # Per-row key chains (the continuous-batching composition seam):
        # every rng consumer below — each round's G draft proposals, the
        # acceptance uniforms, the residual/bonus draw —
        # advances a [B, 2] per-row chain by a FIXED number of
        # split_row_keys steps per round, so a row's sample stream depends
        # only on (its chain start, its round index), never on batch
        # composition. Rounds are batch-synchronized (done rows burn
        # rounds without touching their committed outputs), hence a
        # batched run is BIT-IDENTICAL per row to running that row alone
        # with its chain (tests/test_speculative.py B=1-loop parity).
        # ``rng`` may be one key (chains derived via per_row_keys — the
        # plain sampler's convention) or an already-stacked [B, 2] chain
        # set (the slot engine's convention).
        rng = per_row_keys(rng, B) if jnp.asarray(rng).ndim == 1 else rng
    N = config.max_new_tokens
    G = gamma
    NB = N + G + 1  # token buffer padded so block writes never clip
    S = P + N + G  # cache slots: commits cap at P+N, probes run G past c-1
    V_pad = config.pad_token_id
    input_ids = input_ids.astype(jnp.int32)
    prompt_mask = attention_mask.astype(jnp.int32)

    if drafter is None:
        drafter = model_drafter(draft_apply, G)
    t_cache = init_target_cache(B, S)
    d_cache = init_draft_cache(B, S)
    cache_layout.refuse((t_cache, d_cache), "speculative", S)  # (a window layer's ring takes each row's span at its own index: _ring_plan)

    # ---- prefill both caches over the prompt block ----
    slot0 = jnp.concatenate([prompt_mask, jnp.zeros((B, NB - 1), jnp.int32)], axis=1)
    t_pre = target_apply(
        target_params, input_ids, attention_mask=slot0, positions=None,
        cache=t_cache, cache_index=jnp.asarray(0, jnp.int32), logits_span=(P - 1, P),
    )
    d_state = drafter.prefill(draft_params, t_pre, input_ids, slot0, d_cache)

    def round_step(carry):
        # the shared round (also the CB spec segment's body) — one function,
        # bit-identity by construction
        return spec_round_step(
            carry,
            prompt_mask=prompt_mask,
            target_apply=target_apply,
            target_params=target_params,
            draft_params=draft_params,
            config=config,
            G=G,
            transition_mask=transition_mask,
            adjust_logits=adjust_logits,
            drafter=drafter,
        )

    def cond(carry):
        return ~jnp.all(carry["done"])

    init = {
        "rng": rng,
        "n_out": jnp.zeros((B,), jnp.int32),
        "done": jnp.zeros((B,), bool),
        "t_last": input_ids[:, -1],
        "t_cache": t_pre["cache"],
        "d_cache": d_state,
        "tokens": jnp.full((B, NB), V_pad, jnp.int32),
        "logprobs": jnp.zeros((B, NB), jnp.float32),
        "values": jnp.zeros((B, NB), jnp.float32),
        "mask": jnp.zeros((B, NB), jnp.int32),
        "rounds": jnp.asarray(0, jnp.int32),
        "accepted": jnp.asarray(0, jnp.int32),
        "live_rounds": jnp.asarray(0, jnp.int32),
        "committed": jnp.asarray(0, jnp.int32),
    }
    final = jax.lax.while_loop(cond, round_step, init)

    tokens = final["tokens"][:, :N]
    sequences = jnp.concatenate([input_ids, tokens], axis=1)
    out = GenerationOutput(
        sequences=sequences,
        response_tokens=tokens,
        response_mask=final["mask"][:, :N],
        response_logprobs=final["logprobs"][:, :N],
        response_values=final["values"][:, :N],
        prompt_mask=prompt_mask,
    )
    if return_stats:
        stats = {
            "rounds": final["rounds"],
            "accepted_draft_tokens": final["accepted"],
            # on live rows only: a row that has ended still runs, and counts nowhere
            "proposed_draft_tokens": final["live_rounds"] * G,
            "live_row_rounds": final["live_rounds"],
            # fraction of proposed draft tokens accepted (per live row-round)
            "acceptance_rate": final["accepted"]
            / jnp.maximum(final["live_rounds"] * G, 1),
            # committed tokens per live row-round (throughput multiplier,
            # ∈ [1, G+1] — every live round commits at least the residual)
            "tokens_per_round": final["committed"]
            / jnp.maximum(final["live_rounds"], 1),
        }
        return out, stats
    return out
