"""The gated delta rule with a decay a channel (Kimi Delta Attention, Kimi
Linear report arXiv:2510.26692; a gated delta net, Yang et al.
arXiv:2412.06464, whose forget gate is a vector a head) in plain
``jax.numpy``: the chunked form the prefill, the scoring forward and the train
step run, and the one-token step the decode loop runs.

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

Everything is XLA: no Pallas kernel. All of it is float32 (gates, exponents,
solve, state), and every product runs at ``highest`` precision: a TPU rounds
float32 operands to bf16 otherwise, which reads the carried state as a bf16
state would hold it (on a v5e, 2 rows of 4096 tokens at the published widths:
the final state 2.4e-3 from the token-by-token recurrence at the backend's
default, 1.5e-5 at ``highest``, for 16% more time: PERF.md section 6, PR 54).
"""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

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
    the state answers, read by nobody."""
    with jax.named_scope("trlx/kda_scan"):
        Bsz, T, H, K = k.shape
        V = v.shape[-1]
        C = min(chunk, -(-T // SUB) * SUB)
        sub = min(SUB, C)
        if C % sub:
            raise ValueError(f"kda_chunked: a chunk of {C} is not whole sub-blocks of {sub}")
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
