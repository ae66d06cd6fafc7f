"""The gated delta rule with a decay a channel (Kimi Delta Attention, Kimi
Linear report arXiv:2510.26692; a gated delta net, Yang et al.
arXiv:2412.06464, whose forget gate is a vector a head): the chunked form the
prefill, the scoring forward and the train step run (a Pallas kernel forward
at the published head size, plain ``jax.numpy`` otherwise and backward), and
the one-token step the decode loop runs.

Per head with state ``S [K, V]`` (key channels by value channels), a log decay
``g_t <= 0`` a key channel and a write strength ``beta_t`` in ``[0, 1]``::

    S'_t = Diag(exp(g_t)) S_{t-1}
    S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
    o_t  = S_t^T q_t

The state first forgets, then is corrected by what it does not yet answer for
``k_t``. ``ops/ssd.py`` computes the recurrences whose update is a plain decay
(``S_t = a_t S_{t-1} + k_t v_t^T``, ``a_t`` a scalar a head); this one is not
of that form and shares nothing with it.

**The chunked form.** With ``u_t = beta_t (v_t - S'_t^T k_t)`` (what a token
writes) and ``G_i`` the cumulative log decay inside a chunk, from the state
``S_0`` that enters it::

    A_ij = beta_i sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])        (j < i)
    (I + A) U = beta * (V - (K * exp(G)) S_0)
    o_i = (q_i * exp(G_i)) S_0 + sum_{j <= i} (q_i . (k_j * exp(G_i - G_j))) u_j
    S_C = Diag(exp(G_C)) S_0 + sum_j (k_j * exp(G_C - G_j)) u_j^T

so ``U = T (beta V) - T (beta K exp(G)) S_0`` with ``T = (I + A)^-1``, and
everything but the carry of ``S`` from chunk to chunk runs on all chunks at
once.

**Exact for any gate.** ``exp(G_i - G_j)`` is at most 1 for ``j <= i``, but
its factors ``exp(G_i)`` and ``exp(-G_j)`` are not: the published gate
(``-exp(A_log) softplus(.)``, ``A`` up to 16) passes -88 inside ONE token at
its strongest, where ``exp(-G_j)`` is infinite in float32. No exponent here is
ever positive: a chunk is cut into sub-blocks of ``SUB`` tokens; between two
sub-blocks the exponent is split at the later one's first boundary ``r``
(``(G_i - G_r) + (G_r - G_j)``, both parts at most 0, an underflow of either
is an underflow of the product), which keeps those pairs on the MXU; inside a
sub-block the exponent is formed pair by pair. The gate is never clamped.
``T`` is the nilpotent product ``(I - A)(I + A^2)(I + A^4)...`` (``A`` is
strictly lower triangular, so ``A^C = 0``): ``log2(C)`` squarings and as many
products of ``[C, C]`` matrices on the MXU, where forward substitution would
be ``C`` dependent steps (PERF.md section 6, PR 54).

**Two forms of the chunked pass, one arithmetic.** At a head size of whole
lanes (``scan_takes_kernel``: key and value channels multiples of 128, the
published 128) the forward is a Pallas TPU kernel (``_scan_kernel``; on a TPU
through Mosaic, elsewhere under the Pallas interpreter): per (row, group of
heads) it walks the chunks in order with the heads' states ``[128, 128]``
float32 in VMEM, reads ``q, k, v, g, beta`` once in the mixer's own layout
(``[B, T, H x 128]``, a head a block of whole lanes: nothing is transposed
into a chunk layout) and writes ``o`` and the final state; a chunk's pair
matrices, solve and operands never reach HBM. Any other head size (the toy's
24) runs ``kda_chunked_reference``, the same steps in plain ``jax.numpy``,
which is also what the kernel's backward pass differentiates
(``jax.custom_vjp``: the forward rule keeps the inputs, the backward rule runs
the ``jax.numpy`` pass again and pulls the cotangents through it) and what its
parity tests hold it to (``tests/test_delta_rule_kernel.py``). The choice is
read from the shapes alone; ``learn/kda_scan_pallas`` says which was taken.

All of it is float32 (gates, exponents, solve, state), and every product runs
at ``highest`` precision in both forms (six bf16 passes on the matrix unit): a
TPU rounds float32 operands to bf16 otherwise, which reads the carried state
as a bf16 state would hold it (on a v5e, 2 rows of 4096 tokens at the
published widths: the final state 2.4e-3 from the token-by-token recurrence at
the backend's default, 1.5e-5 at ``highest``: PERF.md section 6, PR 54; the
kernel 7.9e-6 where the ``jax.numpy`` form reads 8.1e-6: PR 55). On that piece
the ``jax.numpy`` form takes 27.0 ms and the kernel 7.3, of which the six
passes of its products are about half (PERF.md section 6, PR 55).
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from trlx_tpu.ops.pallas_utils import pad_to, pltpu, resolve_interpret

F32 = jnp.float32
CHUNK = 64  # tokens of a chunk: one solve, one carry of the state
SUB = 16  # tokens of a sub-block: pairwise exponents inside, one split exponent between two
HIGHEST = jax.lax.Precision.HIGHEST


def _unit_lower_inverse(A: jax.Array) -> jax.Array:
    """``(I + A)^-1`` of strictly lower triangular ``A [..., C, C]``."""
    C = A.shape[-1]
    eye = jnp.eye(C, dtype=A.dtype)
    T, power, reach = eye - A, A, 2  # T = prod_{2^i < reach} (I + (-A)^(2^i)) inverts up to A^reach
    while reach < C:
        power = jnp.matmul(power, power, precision=HIGHEST)
        T = jnp.matmul(T, eye + power, precision=HIGHEST)
        reach *= 2
    return T


def _pair_products(q, k, G, sub):
    """``(A', P')`` of one chunk a row of the leading dims: ``A'_ij = sum_d
    k_i[d] k_j[d] exp(G_i[d] - G_j[d])`` for ``j < i`` and ``P'_ij`` the same
    with ``q_i``, for ``j <= i``; zero above. ``q, k, G [..., C, D]``."""
    C, D = k.shape[-2:]
    ns = C // sub
    lead = k.shape[:-2]
    qs, ks, Gs = (a.reshape(lead + (ns, sub, D)) for a in (q, k, G))
    # inside a sub-block: every pair's own exponent, never positive
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    diff = Gs[..., :, None, :] - Gs[..., None, :, :]  # [..., ns, i, j, D]
    decay = jnp.where(lower[..., None], jnp.exp(jnp.where(lower[..., None], diff, 0.0)), 0.0)
    kd = ks[..., None, :, :] * decay
    a_diag = jnp.sum(ks[..., :, None, :] * kd, axis=-1)  # [..., ns, sub, sub]
    p_diag = jnp.sum(qs[..., :, None, :] * kd, axis=-1)
    a_rows, p_rows = [], []
    for i in range(ns):
        a_row, p_row = [a_diag[..., i, :, :]], [p_diag[..., i, :, :]]
        if i:
            # between sub-blocks: the exponent split at the boundary in front of sub-block i
            at = Gs[..., i - 1, sub - 1, :][..., None, :]  # [..., 1, D]
            after = jnp.exp(Gs[..., i, :, :] - at)  # [..., sub, D], rows of sub-block i
            before = k[..., : i * sub, :] * jnp.exp(at - G[..., : i * sub, :])  # [..., i sub, D]
            both = jnp.concatenate([ks[..., i, :, :] * after, qs[..., i, :, :] * after], axis=-2)
            m = jnp.einsum("...id,...jd->...ij", both, before, preferred_element_type=F32, precision=HIGHEST)
            a_row.insert(0, m[..., :sub, :])
            p_row.insert(0, m[..., sub:, :])
        pad = [(0, 0)] * (len(lead) + 1) + [(0, C - (i + 1) * sub)]
        a_rows.append(jnp.pad(jnp.concatenate(a_row, axis=-1), pad))
        p_rows.append(jnp.pad(jnp.concatenate(p_row, axis=-1), pad))
    strict = jnp.tril(jnp.ones((C, C), F32), -1)
    return jnp.concatenate(a_rows, axis=-2) * strict, jnp.concatenate(p_rows, axis=-2)


def _chunk_of(T: int, chunk: int) -> Tuple[int, int]:
    """``(tokens of a chunk, tokens of a sub-block)`` for a pass of ``T``:
    ``chunk``, or fewer whole sub-blocks where the pass is shorter."""
    C = min(chunk, -(-T // SUB) * SUB)
    sub = min(SUB, C)
    if C % sub:
        raise ValueError(f"kda_chunked: a chunk of {C} is not whole sub-blocks of {sub}")
    return C, sub


def kda_chunked_reference(
    q: jax.Array,  # [B, T, H, K]
    k: jax.Array,  # [B, T, H, K]
    v: jax.Array,  # [B, T, H, V]
    g: jax.Array,  # [B, T, H, K] log decays, <= 0
    beta: jax.Array,  # [B, T, H]
    initial_state: Optional[jax.Array] = None,  # [B, H, K, V] float32
    chunk: int = CHUNK,
) -> Tuple[jax.Array, jax.Array]:
    """``kda_chunked`` in plain ``jax.numpy``: what a head size that is not
    whole lanes runs, what the kernel's backward pass differentiates, and the
    reference its parity tests hold it to."""
    with jax.named_scope("trlx/kda_scan"):
        Bsz, T, H, K = k.shape
        V = v.shape[-1]
        C, sub = _chunk_of(T, chunk)
        g, beta = g.astype(F32), beta.astype(F32)
        nc = -(-T // C)
        pad = nc * C - T

        def chunks(a):  # [B, T, H, ...] -> [B, H, nc, C, ...]
            a = jnp.pad(a.astype(F32), ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            return jnp.moveaxis(a.reshape((Bsz, nc, C) + a.shape[2:]), 3, 1)

        qc, kc, vc, gc, bc = chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta)[..., None]
        G = jnp.cumsum(gc, axis=-2)  # [B, H, nc, C, K]
        a_pairs, p_pairs = _pair_products(qc, kc, G, sub)
        solve = _unit_lower_inverse(bc * a_pairs)  # [B, H, nc, C, C]
        into = jnp.exp(G)  # from the chunk's start to each token
        w = jnp.matmul(solve, bc * kc * into, precision=HIGHEST)  # [.., C, K]
        u0 = jnp.matmul(solve, bc * vc, precision=HIGHEST)  # [.., C, V]
        q_in = qc * into
        total = G[..., -1:, :]  # [B, H, nc, 1, K]
        k_out = kc * jnp.exp(total - G)  # from each token to the chunk's end
        carry_decay = jnp.exp(total[..., 0, :])  # [B, H, nc, K]

        def carry_over(S, xs):
            w_c, u0_c, q_c, p_c, k_c, d_c = xs
            u = u0_c - jnp.einsum("bhck,bhkv->bhcv", w_c, S, precision=HIGHEST)
            o = jnp.einsum("bhck,bhkv->bhcv", q_c, S, precision=HIGHEST) + jnp.einsum("bhcj,bhjv->bhcv", p_c, u, precision=HIGHEST)
            S = d_c[..., None] * S + jnp.einsum("bhck,bhcv->bhkv", k_c, u, precision=HIGHEST)
            return S, o

        s0 = jnp.zeros((Bsz, H, K, V), F32) if initial_state is None else initial_state.astype(F32)
        by_chunk = lambda a: jnp.moveaxis(a, 2, 0)
        final, o = jax.lax.scan(carry_over, s0, tuple(by_chunk(a) for a in (w, u0, q_in, p_pairs, k_out, carry_decay)))
        o = jnp.moveaxis(o, 0, 2)  # [B, H, nc, C, V]
        o = jnp.moveaxis(o, 1, 3).reshape(Bsz, nc * C, H, V)[:, :T]
        return o.astype(v.dtype), final


# The kernel's name in a device trace (``pallas_call(name=...)``: the custom
# call reads ``%kda_scan_fwd.N = (o, f32[rows,heads,K,V]) custom-call(...)``).
KERNEL_NAME = "kda_scan_fwd"
LANES_A_STEP = 1024  # channels of one grid step: 8 heads of 128, whose chains of small products are independent and overlap; 16 outgrow a v5e's VMEM
_ROWS = 8  # rows of a float32 tile: the pairwise exponents run a tile of tokens at a time


def scan_takes_kernel(K: int, V: int) -> bool:
    """Whether ``kda_chunked`` runs the Pallas kernel at these head sizes:
    whole lanes of key and of value channels. Read from the shapes alone."""
    return K % 128 == 0 and V % 128 == 0


def _dot(a, b, contract=((1,), (0,))):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=HIGHEST, preferred_element_type=F32)


def _scan_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref, final_ref, state_ref, *, heads, C, sub, K, V):
    """One chunk of ``heads`` heads of one row: grid ``(row, head group,
    chunk)``, the chunks in order. ``state_ref [heads, V, K]`` carries each
    head's state TRANSPOSED (value channels by key channels), so that a
    chunk's decay a key channel multiplies along the lanes.

    A head's chunk is a chain of small dependent products (ten for the solve
    alone), each far shorter than a matrix unit's latency: the code below
    runs every stage for all the heads before the next stage, so that the
    heads' chains stand side by side in program order and overlap on the
    chip's four matrix units. Head by head, each chain waited on its own
    products: 10.1 ms a piece of 2 x 4096 tokens against 7.3 (PERF.md
    section 6, PR 55)."""
    c = pl.program_id(2)
    hs = range(heads)

    @pl.when(c == 0)
    def _():
        for h in hs:
            state_ref[h] = s0_ref[0, h].T

    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye = (row == col).astype(F32)
    token = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    tile_row = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, 1), 0)
    tile_col = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, C), 1)
    keys = lambda ref, h: ref[0, :, h * K : (h + 1) * K].astype(F32)
    q, k, v = [keys(q_ref, h) for h in hs], [keys(k_ref, h) for h in hs], [v_ref[0, :, h * V : (h + 1) * V].astype(F32) for h in hs]
    beta = [beta_ref[0, 0, :, h : h + 1].astype(F32) for h in hs]  # [C, 1]
    upto = (row >= col).astype(F32)
    G = [_dot(upto, keys(g_ref, h)) for h in hs]  # the cumulative log decay: exact products, float32 sums

    a_tiles, p_tiles = [[] for _ in hs], [[] for _ in hs]
    for start in range(0, C, sub):
        rows = slice(start, start + sub)
        ahead = [jnp.zeros((2 * sub, C), F32)] * heads
        if start:  # the sub-blocks in front: the exponent split at the boundary, both parts at most 0
            at = [G[h][start - 1 : start] for h in hs]
            after = [jnp.exp(G[h][rows] - at[h]) for h in hs]
            before = [jnp.where(token < start, k[h] * jnp.exp(jnp.minimum(at[h] - G[h], 0.0)), 0.0) for h in hs]  # [C, K]
            ahead = [_dot(jnp.concatenate([k[h][rows] * after[h], q[h][rows] * after[h]], axis=0), before[h], ((1,), (1,))) for h in hs]
        for h in hs:  # inside the sub-block: every pair's own exponent, a tile of rows at a time
            for first in range(start, start + sub, _ROWS):
                tile, at_k, at_q = slice(first, first + _ROWS), first - start, sub + first - start
                G_t, k_t, q_t = G[h][tile], k[h][tile], q[h][tile]
                a_t, p_t = ahead[h][at_k : at_k + _ROWS], ahead[h][at_q : at_q + _ROWS]
                for j in range(start, first + _ROWS):
                    live = tile_row + first >= j
                    kd = k[h][j : j + 1] * jnp.where(live, jnp.exp(jnp.where(live, G_t - G[h][j : j + 1], 0.0)), 0.0)
                    here = tile_col == j
                    a_t = jnp.where(here, jnp.sum(k_t * kd, axis=1, keepdims=True), a_t)
                    p_t = jnp.where(here, jnp.sum(q_t * kd, axis=1, keepdims=True), p_t)
                a_tiles[h].append(a_t)
                p_tiles[h].append(p_t)
    power = [beta[h] * jnp.where(row > col, jnp.concatenate(a_tiles[h], axis=0), 0.0) for h in hs]
    P = [jnp.concatenate(p_tiles[h], axis=0) for h in hs]
    solve, reach = [eye - A for A in power], 2  # `_unit_lower_inverse`
    while reach < C:
        power = [_dot(A, A) for A in power]
        solve = [_dot(T, eye + A) for T, A in zip(solve, power)]
        reach *= 2
    into = [jnp.exp(G[h]) for h in hs]
    total = [G[h][C - 1 : C] for h in hs]  # [1, K]
    w = [_dot(solve[h], beta[h] * k[h] * into[h]) for h in hs]
    u0 = [_dot(solve[h], beta[h] * v[h]) for h in hs]
    S = [state_ref[h] for h in hs]  # [V, K]
    answers = [_dot(jnp.concatenate([w[h], q[h] * into[h]], axis=0), S[h], ((1,), (1,))) for h in hs]  # what the state answers for w and for q
    u = [u0[h] - answers[h][:C] for h in hs]
    o = [answers[h][C:] + _dot(P[h], u[h]) for h in hs]
    S = [S[h] * jnp.exp(total[h]) + _dot(u[h], k[h] * jnp.exp(total[h] - G[h]), ((0,), (0,))) for h in hs]
    for h in hs:
        state_ref[h] = S[h]
        o_ref[0, :, h * V : (h + 1) * V] = o[h].astype(o_ref.dtype)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        for h in hs:
            final_ref[0, h] = state_ref[h].T


@functools.partial(jax.jit, static_argnums=(6, 7))  # traced once a shape, not once a layer a program: a trace of the unrolled heads takes seconds
@jax.named_scope("trlx/kda_scan")
def _scan_pallas(q, k, v, g, beta, s0, chunk: int, interpret: bool):
    """The kernel on ``q, k, g [B, T, H, K]``, ``v [B, T, H, V]``, ``beta [B,
    T, H]``, ``s0 [B, H, K, V]``: the mixer's own layout, a head a block of
    whole lanes of ``[B, T, H x K]``, so nothing is transposed on the way in
    or out."""
    Bsz, T, H, K = k.shape
    V = v.shape[-1]
    C, sub = _chunk_of(T, chunk)
    if sub % _ROWS:
        raise ValueError(f"kda_chunked: the kernel runs sub-blocks of whole tiles of {_ROWS} tokens, not {sub}")
    heads = next(n for n in range(max(1, LANES_A_STEP // max(K, V)), 0, -1) if H % n == 0)
    nc = -(-T // C)

    def flat(a):  # [B, T, H, D] -> [B, nc C, H D]: padded tokens neither decay nor write (g = 0, beta = 0)
        return pad_to(a.reshape(Bsz, T, -1), C, 1)

    beta = jnp.moveaxis(flat(beta.astype(F32)).reshape(Bsz, nc * C, H // heads, heads), 2, 1)  # [B, groups, T, heads]: 4 bytes a head a token
    keys = pl.BlockSpec((1, C, heads * K), lambda b, hg, c: (b, c, hg))
    values = pl.BlockSpec((1, C, heads * V), lambda b, hg, c: (b, c, hg))
    state = pl.BlockSpec((1, heads, K, V), lambda b, hg, c: (b, hg, 0, 0))
    kernel = functools.partial(_scan_kernel, heads=heads, C=C, sub=sub, K=K, V=V)
    o, final = pl.pallas_call(
        kernel,
        grid=(Bsz, H // heads, nc),
        in_specs=[keys, keys, values, keys, pl.BlockSpec((1, 1, C, heads), lambda b, hg, c: (b, hg, c, 0)), state],
        out_specs=[values, state],
        out_shape=[jax.ShapeDtypeStruct((Bsz, nc * C, H * V), v.dtype), jax.ShapeDtypeStruct((Bsz, H, K, V), F32)],
        scratch_shapes=[pltpu.VMEM((heads, V, K), F32)],
        compiler_params=None if interpret else pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(flat(q), flat(k), flat(v), flat(g.astype(F32)), beta, s0.astype(F32))
    return o[:, :T].reshape(Bsz, T, H, V), final


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan_with_xla_backward(q, k, v, g, beta, s0, chunk, interpret):
    return _scan_pallas(q, k, v, g, beta, s0, chunk, interpret)


def _scan_fwd_rule(q, k, v, g, beta, s0, chunk, interpret):
    return _scan_pallas(q, k, v, g, beta, s0, chunk, interpret), (q, k, v, g, beta, s0)


def _scan_bwd_rule(chunk, interpret, kept, cotangents):
    """The backward pass differentiates the ``jax.numpy`` form (which runs
    the pass again): the kernel is the forward alone."""
    _, pull = jax.vjp(functools.partial(kda_chunked_reference, chunk=chunk), *kept)
    return pull(cotangents)


_scan_with_xla_backward.defvjp(_scan_fwd_rule, _scan_bwd_rule)


def kda_chunked(
    q: jax.Array,  # [B, T, H, K]
    k: jax.Array,  # [B, T, H, K]
    v: jax.Array,  # [B, T, H, V]
    g: jax.Array,  # [B, T, H, K] log decays, <= 0
    beta: jax.Array,  # [B, T, H]
    initial_state: Optional[jax.Array] = None,  # [B, H, K, V] float32
    chunk: int = CHUNK,
) -> Tuple[jax.Array, jax.Array]:
    """``(o [B, T, H, V] in v's dtype, final state [B, H, K, V] float32)``.
    A padded token is the caller's to mask: with ``g = 0`` and ``beta = 0`` it
    neither decays the state nor writes to it, and its own output is whatever
    the state answers, read by nobody.

    Heads of whole lanes (``scan_takes_kernel``) take the Pallas kernel, on a
    TPU through Mosaic and elsewhere under the interpreter; any other head
    size takes ``kda_chunked_reference``."""
    Bsz, _, H, K = k.shape
    V = v.shape[-1]
    if not scan_takes_kernel(K, V):
        return kda_chunked_reference(q, k, v, g, beta, initial_state, chunk)
    s0 = jnp.zeros((Bsz, H, K, V), F32) if initial_state is None else initial_state
    return _scan_with_xla_backward(q, k, v, g, beta, s0, chunk, resolve_interpret(None))


def kda_step(
    state: jax.Array,  # [B, H, K, V] float32
    q: jax.Array,  # [B, H, K]
    k: jax.Array,  # [B, H, K]
    v: jax.Array,  # [B, H, V]
    g: jax.Array,  # [B, H, K] log decays
    beta: jax.Array,  # [B, H]
) -> Tuple[jax.Array, jax.Array]:
    """One token of the recurrence: ``(o [B, H, V], new state)``, float32
    throughout (the step is bound by reading and writing the state)."""
    with jax.named_scope("trlx/kda_step"):
        qf, kf, vf = q.astype(F32), k.astype(F32), v.astype(F32)
        state = state * jnp.exp(g.astype(F32))[..., None]
        u = beta.astype(F32)[..., None] * (vf - jnp.einsum("bhkv,bhk->bhv", state, kf))
        state = state + kf[..., None] * u[..., None, :]
        return jnp.einsum("bhkv,bhk->bhv", state, qf).astype(v.dtype), state
