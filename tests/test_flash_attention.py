"""Flash-attention kernel vs the naive XLA oracle (interpret mode on CPU).

The reference relies on CUDA fused attention inside HF transformers
(SURVEY.md §2.4); here the fused op is ours, so it gets direct numerics
tests: forward, logsumexp, gradients, ALiBi, offsets (ring contract),
left-padded masks.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.ops.flash_attention import attention_reference, flash_attention
from trlx_tpu.models.transformer import alibi_slopes


def _rand(key, *shape):
    return jax.random.normal(key, shape, jnp.float32)


def _mk(B=2, T=16, S=16, H=2, D=8, left_pad=0, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = _rand(ks[0], B, T, H, D)
    k = _rand(ks[1], B, S, H, D)
    v = _rand(ks[2], B, S, H, D)
    mask = np.ones((B, S), np.float32)
    if left_pad:
        mask[:, :left_pad] = 0.0
        mask[0, : left_pad + 2] = 0.0  # ragged padding across the batch
    return q, k, v, jnp.asarray(mask)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("left_pad", [0, 3])
def test_forward_matches_reference(causal, left_pad):
    q, k, v, mask = _mk(left_pad=left_pad)
    out, lse = flash_attention(
        q, k, v, mask, causal=causal, interpret=True, return_lse=True,
        block_q=8, block_k=8,
    )
    ref, ref_lse = attention_reference(q, k, v, mask, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )
    # valid rows only: padded/fully-masked rows hold sentinel values
    valid = np.asarray(lse) > -1e29
    np.testing.assert_allclose(
        np.asarray(lse)[valid], np.asarray(ref_lse)[valid], atol=2e-5, rtol=2e-5
    )


def test_offsets_match_shifted_slots():
    """q/k slot offsets reproduce a contiguous chunk of a bigger sequence —
    the contract ring attention depends on."""
    B, T, H, D = 1, 16, 2, 8
    q, k, v, mask = _mk(B=B, T=T, S=T, H=H, D=D, seed=3)
    full, _ = attention_reference(q, k, v, mask, causal=True)
    # split keys in two chunks, query chunk is the second half of slots
    qh = q[:, 8:]
    out0, lse0 = flash_attention(
        qh, k[:, :8], v[:, :8], mask[:, :8], causal=True,
        q_offset=8, k_offset=0, interpret=True, return_lse=True,
        block_q=8, block_k=8,
    )
    out1, lse1 = flash_attention(
        qh, k[:, 8:], v[:, 8:], mask[:, 8:], causal=True,
        q_offset=8, k_offset=8, interpret=True, return_lse=True,
        block_q=8, block_k=8,
    )
    # combine the two normalized chunks via logsumexp weights
    m = jnp.maximum(lse0, lse1)
    w0 = jnp.exp(lse0 - m)[..., None]
    w1 = jnp.exp(lse1 - m)[..., None]
    out0t = out0.transpose(0, 2, 1, 3)
    out1t = out1.transpose(0, 2, 1, 3)
    comb = ((out0t * w0 + out1t * w1) / (w0 + w1)).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(comb), np.asarray(full[:, 8:]), atol=2e-5, rtol=2e-5
    )


def test_alibi_matches_reference():
    B, T, H, D = 2, 16, 4, 8
    q, k, v, mask = _mk(B=B, T=T, S=T, H=H, D=D, left_pad=2, seed=5)
    slopes = jnp.asarray(alibi_slopes(H), jnp.float32)
    kpos = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0).astype(jnp.int32)
    qpos = kpos
    out = flash_attention(
        q, k, v, mask, causal=True, q_positions=qpos, k_positions=kpos,
        alibi_slopes=slopes, interpret=True, block_q=8, block_k=8,
    )
    ref, _ = attention_reference(
        q, k, v, mask, causal=True, q_positions=qpos, k_positions=kpos,
        alibi_slopes=slopes,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("left_pad", [0, 3])
def test_gradients_match_reference(left_pad):
    q, k, v, mask = _mk(T=16, S=16, left_pad=left_pad, seed=7)

    def loss_flash(q, k, v):
        out = flash_attention(
            q, k, v, mask, causal=True, interpret=True, block_q=8, block_k=8
        )
        return jnp.sum(out * out)

    def loss_ref(q, k, v):
        out, _ = attention_reference(q, k, v, mask, causal=True)
        return jnp.sum(out * out)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-5, rtol=5e-5,
            err_msg=f"grad mismatch for {name}",
        )


@pytest.mark.parametrize("window", [1, 4, 7, 16])
@pytest.mark.parametrize("left_pad", [0, 3])
def test_sliding_window_matches_reference(window, left_pad):
    """Windowed masking (mistral family): forward + both gradients against
    the oracle, across window widths from degenerate (1 = self only) to
    no-op (>= T), with ragged left padding."""
    q, k, v, mask = _mk(T=16, S=16, left_pad=left_pad, seed=5)

    def loss_flash(q, k, v):
        out = flash_attention(
            q, k, v, mask, causal=True, interpret=True, block_q=8, block_k=8,
            window=window,
        )
        return jnp.sum(out * out), out

    def loss_ref(q, k, v):
        out, _ = attention_reference(q, k, v, mask, causal=True, window=window)
        return jnp.sum(out * out), out

    (_, out_f), g_flash = jax.value_and_grad(loss_flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, out_r), g_ref = jax.value_and_grad(loss_ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r), atol=2e-5, rtol=2e-5)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-5, rtol=5e-5,
            err_msg=f"window={window} grad mismatch for {name}",
        )


def test_sliding_window_with_offsets():
    """Window + slot offsets compose (the ring-attention chunk contract):
    chunked windowed attention reproduces the monolithic windowed result."""
    B, T, H, D = 1, 16, 2, 8
    q, k, v, mask = _mk(B=B, T=T, S=T, H=H, D=D, seed=9)
    full, _ = attention_reference(q, k, v, mask, causal=True, window=6)
    qh = q[:, 8:]
    o1, l1 = flash_attention(
        qh, k[:, :8], v[:, :8], mask[:, :8], causal=True, q_offset=8, k_offset=0,
        interpret=True, block_q=8, block_k=8, return_lse=True, window=6,
    )
    o2, l2 = flash_attention(
        qh, k[:, 8:], v[:, 8:], mask[:, 8:], causal=True, q_offset=8, k_offset=8,
        interpret=True, block_q=8, block_k=8, return_lse=True, window=6,
    )
    # combine the two chunk results with the online-softmax rule
    m = jnp.maximum(l1, l2)
    w1 = jnp.exp(l1 - m)[..., None].transpose(0, 2, 1, 3)
    w2 = jnp.exp(l2 - m)[..., None].transpose(0, 2, 1, 3)
    combined = (o1 * w1 + o2 * w2) / (w1 + w2)
    np.testing.assert_allclose(
        np.asarray(combined), np.asarray(full[:, 8:]), atol=2e-5, rtol=2e-5
    )


def test_nondivisible_lengths_pad():
    q, k, v, mask = _mk(T=13, S=13, seed=11)
    out = flash_attention(
        q, k, v, mask, causal=True, interpret=True, block_q=8, block_k=8
    )
    ref, _ = attention_reference(q, k, v, mask, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_model_pallas_path_matches_xla():
    """Full CausalTransformer forward with attention_impl='pallas'
    (interpret mode on CPU) matches the xla einsum path, including on a
    left-padded batch and for the hydra branch replay."""
    from trlx_tpu.models.transformer import CausalTransformer, config_from_spec

    cfg_x = config_from_spec("builtin:bloom-test", dtype=jnp.float32, attention_impl="xla")
    cfg_p = dataclasses_replace(cfg_x, attention_impl="pallas")
    model_x = CausalTransformer(cfg_x)
    model_p = CausalTransformer(cfg_p)
    B, T = 2, 12
    ids = jax.random.randint(jax.random.PRNGKey(0), (B, T), 0, cfg_x.vocab_size)
    mask = jnp.ones((B, T), jnp.int32).at[0, :4].set(0)
    params = model_x.init(jax.random.PRNGKey(1), ids)["params"]
    out_x = model_x.apply({"params": params}, ids, attention_mask=mask, branch_layer=1)
    out_p = model_p.apply({"params": params}, ids, attention_mask=mask, branch_layer=1)
    lx = np.asarray(out_x["logits"], np.float32)
    lp = np.asarray(out_p["logits"], np.float32)
    valid = np.asarray(mask) > 0
    np.testing.assert_allclose(lp[valid], lx[valid], atol=2e-4, rtol=2e-4)

    bx = model_x.apply(
        {"params": params}, out_x["branch_input"], 1, mask,
        method=CausalTransformer.forward_branch,
    )
    bp = model_p.apply(
        {"params": params}, out_p["branch_input"], 1, mask,
        method=CausalTransformer.forward_branch,
    )
    np.testing.assert_allclose(
        np.asarray(bp["logits"], np.float32)[valid],
        np.asarray(bx["logits"], np.float32)[valid],
        atol=2e-4, rtol=2e-4,
    )


def dataclasses_replace(cfg, **kw):
    import dataclasses

    return dataclasses.replace(cfg, **kw)


@pytest.mark.parametrize("kv_heads", [1, 2])
def test_gqa_unrepeated_kv_matches_repeated(kv_heads):
    """Kernels consume grouped-query K/V natively (no jnp.repeat): forward and
    all gradients must match the repeated-KV oracle, with dk/dv group-summed."""
    B, T, H, D = 2, 16, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, kv_heads, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, kv_heads, D), jnp.float32)
    mask = jnp.ones((B, T), jnp.float32).at[0, :3].set(0)
    reps = H // kv_heads

    def loss_gqa(q, k, v):
        out = flash_attention(q, k, v, mask, causal=True, interpret=True,
                              block_q=8, block_k=8)
        return jnp.sum(out ** 2)

    def loss_ref(q, k, v):
        out, _ = attention_reference(
            q, jnp.repeat(k, reps, axis=2), jnp.repeat(v, reps, axis=2),
            mask, causal=True,
        )
        return jnp.sum(out ** 2)

    np.testing.assert_allclose(loss_gqa(q, k, v), loss_ref(q, k, v), rtol=1e-5)
    g = jax.grad(loss_gqa, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5,
            err_msg=f"GQA grad mismatch for {name}",
        )


# ---------------------------------------------------------------------------
# bf16 inputs: float32 tiles inside the kernel, bf16 results
# ---------------------------------------------------------------------------

BF16_REL_L2 = 1e-2
BF16_MAX_ABS = 4e-2
BF16_T = 32


def _bf16_case(D, group, window, variant, seed=0):
    """Inputs of one bf16 case and the keywords both implementations take.
    ``offsets``: the queries are the second half of the row's slots, the keys
    the whole row (the ring-attention chunk contract)."""
    T = S = BF16_T
    B, KV = 2, 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    kw = {}
    if variant == "offsets":
        T = S // 2
        kw.update(q_offset=S - T, k_offset=0)
    q = jax.random.normal(ks[0], (B, T, KV * group, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.bfloat16)
    mask = np.ones((B, S), np.float32)
    if variant in ("left_pad", "alibi"):
        mask[:, :3] = 0.0
        mask[0, :5] = 0.0
    mask = jnp.asarray(mask)
    if variant == "alibi":
        pos = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0).astype(jnp.int32)
        kw.update(
            q_positions=pos, k_positions=pos,
            alibi_slopes=jnp.asarray(alibi_slopes(KV * group), jnp.float32),
        )
    if window is not None:
        kw["window"] = window
    return q, k, v, mask, kw


def _close_bf16(got, want, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(float(np.abs(want).max()), 1e-6)
    rel = float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-6))
    assert rel < BF16_REL_L2, f"{what}: relative L2 {rel}"
    assert float(np.abs(got - want).max()) < BF16_MAX_ABS * scale, f"{what}: max abs"


@pytest.mark.parametrize(
    "window,variant",
    [(None, "plain"), (40, "plain"), (9, "plain"), (None, "left_pad"), (9, "left_pad"), (None, "alibi"), (9, "offsets")],
    ids=["plain", "window_inside", "window_binding", "left_pad", "left_pad_window", "alibi", "offsets_window"],
)
@pytest.mark.parametrize("D,group", [(128, 1), (128, 4), (128, 7), (256, 1), (256, 4)])
def test_bf16_inputs_match_reference(D, group, window, variant):
    """bf16 q, k, v, do: forward, ``lse`` and all three gradients against
    ``attention_reference`` on the SAME bf16 inputs (the reference computes
    in float32 throughout). The kernel converts each tile to float32 and
    contracts float32 operands, which the interpreter here does exactly (so
    ``lse``, which never leaves float32, keeps a float32 tolerance) and a
    v5e's MXU in one bf16 pass; its results are rounded to bf16 on the way
    out, 2^-9 relative a value. An output or a gradient is held to 1e-2 in
    relative L2 and to 4e-2 of the largest entry elementwise, which leaves
    the chip's pass its room (measured here: at most 4.5e-3 and 1.1e-2)."""
    q, k, v, mask, kw = _bf16_case(D, group, window, variant)
    reps = group

    def ref_fn(q, k, v):
        return attention_reference(
            q, jnp.repeat(k, reps, axis=2), jnp.repeat(v, reps, axis=2), mask, causal=True, **kw
        )

    out, lse = flash_attention(
        q, k, v, mask, causal=True, interpret=True, return_lse=True, block_q=16, block_k=16, **kw
    )
    assert out.dtype == jnp.bfloat16 and lse.dtype == jnp.float32
    ref, ref_lse = ref_fn(q, k, v)
    valid = np.asarray(ref_lse) > -1e29
    _close_bf16(np.asarray(out, np.float32).transpose(0, 2, 1, 3)[valid], np.asarray(ref).transpose(0, 2, 1, 3)[valid], "out")
    np.testing.assert_allclose(np.asarray(lse)[valid], np.asarray(ref_lse)[valid], atol=1e-4, rtol=1e-4)

    do = jax.random.normal(jax.random.PRNGKey(7), out.shape, jnp.bfloat16)
    # cotangent only on rows that see a key: a fully padded row's output is
    # not part of any loss
    do = do * jnp.asarray(valid.transpose(0, 2, 1)[..., None], jnp.bfloat16)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, mask, causal=True, interpret=True, block_q=16, block_k=16, **kw)
        return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32))

    def loss_ref(q, k, v):
        return jnp.sum(ref_fn(q, k, v)[0] * do.astype(jnp.float32))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        assert gf.dtype == jnp.bfloat16
        _close_bf16(gf, gr, f"d{name}")


def _kernel_eqns(fn, *args):
    """Every equation of the Pallas kernels ``fn`` traces, loops included."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    calls = [e for e in walk(jax.make_jaxpr(fn)(*args).jaxpr) if e.primitive.name == "pallas_call"]
    assert calls
    return [e for call in calls for e in walk(call.params["jaxpr"])]


def _float_converts(eqns):
    return [
        (e.invars[0].aval.dtype, e.params["new_dtype"], e.invars[0].aval.shape)
        for e in eqns
        if e.primitive.name == "convert_element_type"
        and jnp.issubdtype(e.invars[0].aval.dtype, jnp.floating)
        and jnp.issubdtype(e.params["new_dtype"], jnp.floating)
        and e.invars[0].aval.dtype != e.params["new_dtype"]  # not a weak-type change
    ]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "float32"])
def test_kernel_arithmetic_is_float32_by_jaxpr(dtype):
    """ONE arithmetic whatever the inputs' dtype, read off the kernels' jaxprs
    (forward and fused backward, block 16, head size 32): every contraction
    takes float32 operands into a float32 result with no precision asked for,
    q is scaled once a tile and the scores never, and the only float
    conversions are the bf16 operands' up to float32 (operand shaped, none
    for float32 inputs) and the results' down on the way out."""
    B, T, H, D, blk = 1, 32, 2, 32, 16
    q = jnp.ones((B, T, H, D), dtype)
    mask = jnp.ones((B, T), jnp.float32)

    def loss(q, k, v):
        out = flash_attention(q, k, v, mask, interpret=True, block_q=blk, block_k=blk, window=20)
        return out.astype(jnp.float32).sum()

    # other test files of the same worker raise jax_default_matmul_precision
    # at import, and a dot that asks for none follows the default
    with jax.default_matmul_precision(None):
        eqns = _kernel_eqns(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    # two in each of the forward's bodies (edge, interior, edge), five in the backward's
    assert len(dots) == 3 * 2 + 3 * 5
    for e in dots:
        assert [v.aval.dtype for v in e.invars] == [jnp.float32, jnp.float32]
        assert e.outvars[0].aval.dtype == jnp.float32
        assert e.params["precision"] is None
    scaled_scores = [
        e for e in eqns
        if e.primitive.name == "mul" and e.outvars[0].aval.shape == (blk, blk)
        and any(getattr(v, "val", None) is not None and np.ndim(v.val) == 0 and abs(float(v.val) - D ** -0.5) < 1e-6 for v in e.invars)
    ]
    assert scaled_scores == []
    converts = _float_converts(eqns)
    if dtype == jnp.float32:
        assert converts == []
    else:
        up = [c for c in converts if c[1] == jnp.float32]
        down = [c for c in converts if c[1] == jnp.bfloat16]
        assert len(up) + len(down) == len(converts) and up and down
        assert all(src == jnp.bfloat16 for src, _, _ in up) and all(src == jnp.float32 for src, _, _ in down)
        assert {shape for _, _, shape in converts} == {(blk, D)}  # operand tiles in, o / dk / dv out


@pytest.mark.parametrize(
    "causal,window,variant",
    [(True, None, "plain"), (True, 40, "plain"), (True, 9, "left_pad"), (True, None, "alibi"), (True, 9, "offsets"), (False, None, "left_pad")],
    ids=["causal", "window_inside", "window_binding_left_pad", "alibi", "offsets_window", "not_causal"],
)
def test_split_walk_equals_the_single_masked_loop_to_the_bit(monkeypatch, causal, window, variant):
    """The interior / edge split changes no value. With ``_walk_tiles``
    replaced by the ONE loop over ``[lo, hi)`` that masks every tile by
    position (the loop the kernels had before the split), float32 inputs give
    bit-equal ``out``, ``lse``, ``dq``, ``dk``, ``dv``: the same tiles in the
    same order, and an interior tile's positional mask is all true. This is
    the float32 contract ring attention relies on, held exactly and not to
    2e-5."""
    from trlx_tpu.ops import flash_attention as fa

    q, k, v, mask, kw = _bf16_case(32, 4, window, variant)
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    do = jax.random.normal(jax.random.PRNGKey(3), q.shape, jnp.float32)

    def run():
        call = functools.partial(
            flash_attention, key_mask=mask, causal=causal, interpret=True, block_q=8, block_k=8, **kw
        )
        out, lse = call(q, k, v, return_lse=True)  # the ring's entry: no vjp
        grads = jax.grad(lambda q, k, v: jnp.sum(call(q, k, v) * do), argnums=(0, 1, 2))(q, k, v)
        return [np.asarray(x) for x in (out, lse, *grads)]

    split = run()
    calls = []

    def one_masked_loop(tile, bounds, carry, positional):
        calls.append(positional)
        return jax.lax.fori_loop(bounds[0], bounds[3], functools.partial(tile, positional=positional), carry)

    monkeypatch.setattr(fa, "_walk_tiles", one_masked_loop)
    single = run()
    assert len(calls) == 3  # two forwards and the backward, each traced anew
    for a, b, name in zip(split, single, ("out", "lse", "dq", "dk", "dv")):
        assert np.array_equal(a, b), name


def _pair_visibility(T, S, qoff, koff, window):
    """``[T, S]`` bools: which (query slot, key slot) pairs positions allow."""
    q_slots = np.arange(T)[:, None] + qoff
    k_slots = np.arange(S)[None, :] + koff
    visible = k_slots <= q_slots
    if window:
        visible &= q_slots - k_slots < window
    return visible


@pytest.mark.parametrize("window", [None, 1, 100, 512, 700, 4096])
@pytest.mark.parametrize(
    "T,S",
    [(128, 128), (200, 200), (384, 384), (640, 640), (768, 768), (896, 896), (1024, 1024), (1100, 1100),
     (1152, 1152), (1280, 1280), (1408, 1408), (2048, 2048), (128, 640), (384, 640), (896, 1024), (1536, 2048)],
)
def test_tile_walk_against_a_brute_force_walk_of_the_mask(T, S, window):
    """For the tile ``choose_blocks`` returns at each shape (every tile it
    can return is among them: the row itself up to 896 slots; beyond, the
    largest of 512, 384, 256 and 128 that divides the row; a divisor for the
    keys of a prefill), the bounds the kernels walk
    (``_fwd_tile_bounds`` and ``_bwd_tile_bounds``: the kernels call these
    very functions) against the mask itself: every tile with a visible pair
    is visited, every interior tile has no pair that position masks, and the
    visited set is no wider than the tiles the diagonal and the window's edge
    touch. ``block_pairs_visited`` is the forward's sum at offset 0."""
    from trlx_tpu.ops import flash_attention as fa

    block_q, block_k = fa.choose_blocks(T, S)
    assert block_q % 128 == 0 and block_k % 128 == 0 and block_k <= block_q
    Tp, Sp = -(-T // block_q) * block_q, -(-S // block_k) * block_k
    # no row is padded further than its 128 lanes ask
    assert (Tp, Sp) == (-(-T // 128) * 128, -(-S // 128) * 128)
    n_q, n_k = Tp // block_q, Sp // block_k
    for qoff in {0, S - T}:
        visible = _pair_visibility(Tp, Sp, qoff, 0, window)
        tiles = visible.reshape(n_q, block_q, n_k, block_k)
        some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
        visited = np.zeros((n_q, n_k), bool)
        interior = np.zeros((n_q, n_k), bool)
        for iq in range(n_q):
            lo, lo_in, hi_in, hi = fa._fwd_tile_bounds(
                qoff + iq * block_q, 0, block_q, block_k, n_k, True, window
            )
            assert 0 <= lo <= lo_in <= hi_in <= hi <= n_k
            visited[iq, lo:hi] = True
            interior[iq, lo_in:hi_in] = True
        assert (visited == some).all()  # exactly the tiles with a visible pair
        assert (interior == every).all()  # exactly the tiles position leaves whole
        back_visited = np.zeros((n_q, n_k), bool)
        back_interior = np.zeros((n_q, n_k), bool)
        for ik in range(n_k):
            lo, lo_in, hi_in, hi = fa._bwd_tile_bounds(
                ik * block_k, qoff, block_q, block_k, n_q, True, window
            )
            assert 0 <= lo <= lo_in <= hi_in <= hi <= n_q
            back_visited[lo:hi, ik] = True
            back_interior[lo_in:hi_in, ik] = True
        # the backward's range is contiguous in q, so it may take in a masked
        # tile between two visible ones only if the mask is not convex: it is
        assert (back_visited == some).all() and (back_interior == every).all()
        if T == S and qoff == 0:
            causal = _pair_visibility(Tp, Sp, 0, 0, None).reshape(n_q, block_q, n_k, block_k).any(axis=(1, 3))
            assert fa.block_pairs_visited(T, window, block_q, block_k) == (some.sum(), causal.sum(), every.sum())


def test_explicit_tiles_are_honoured_and_none_asks_the_chooser():
    from trlx_tpu.ops import flash_attention as fa

    assert fa._resolve_blocks(None, None, 8192, 8192, False) == fa.choose_blocks(8192, 8192) == (512, 512)
    assert fa._resolve_blocks(128, 256, 8192, 8192, False) == (128, 256)
    assert fa._resolve_blocks(None, None, 13, 13, True) == (13, 13)  # the interpreter: no wider than the row
    # what set the thresholds (PERF.md section 6, PR 34): one tile up to 896 slots, 512 beyond
    assert [fa.choose_blocks(n, n) for n in (128, 384, 640, 896, 1024, 6144)] == [
        (128, 128), (384, 384), (640, 640), (896, 896), (512, 512), (512, 512)]
    # a long row that 512 does not divide takes the largest tile that does
    assert [fa.choose_blocks(n, n) for n in (1100, 1152, 1280, 1408, 1536)] == [
        (384, 384), (384, 384), (256, 256), (128, 128), (512, 512)]
    assert fa.choose_blocks(128, 640) == (128, 128) and fa.choose_blocks(896, 1024) == (896, 512)


# ---------------------------------------------------------------------------
# unlike head sizes: q and k of one size, v (and the output) of another
# ---------------------------------------------------------------------------


def _mk_unlike(D, Dv, B=2, T=24, S=24, H=2, KV=2, left_pad=0, seed=3, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _rand(ks[0], B, T, H, D).astype(dtype)
    k = _rand(ks[1], B, S, KV, D).astype(dtype)
    v = _rand(ks[2], B, S, KV, Dv).astype(dtype)
    mask = np.ones((B, S), np.float32)
    if left_pad:
        mask[:, :left_pad] = 0.0
        mask[0, : left_pad + 2] = 0.0
    return q, k, v, jnp.asarray(mask)


@pytest.mark.parametrize("left_pad", [0, 3])
@pytest.mark.parametrize("D,Dv", [(24, 16), (16, 24), (192, 128)], ids=["24_16", "16_24", "192_128"])
def test_unlike_head_sizes_forward_matches_reference(D, Dv, left_pad):
    q, k, v, mask = _mk_unlike(D, Dv, left_pad=left_pad)
    out, lse = flash_attention(q, k, v, mask, causal=True, interpret=True, block_q=8, block_k=8,
                               return_lse=True)
    ref, ref_lse = attention_reference(q, k, v, mask, causal=True)
    assert out.shape == (2, 24, 2, Dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("window", [None, 5])
@pytest.mark.parametrize("D,Dv,KV", [(24, 16, 2), (24, 16, 1), (192, 128, 2)], ids=["24_16", "24_16_gqa", "192_128"])
def test_unlike_head_sizes_gradients_match_reference(D, Dv, KV, window):
    q, k, v, mask = _mk_unlike(D, Dv, KV=KV, left_pad=3, seed=5)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    if KV == 1:
        repeat = lambda a: jnp.repeat(a, 2, axis=2)
        ref_fn = lambda q, k, v: attention_reference(q, repeat(k), repeat(v), mask, causal=True, window=window)[0]
    else:
        ref_fn = lambda q, k, v: attention_reference(q, k, v, mask, causal=True, window=window)[0]
    flash_fn = lambda q, k, v: flash_attention(q, k, v, mask, causal=True, interpret=True,
                                               block_q=8, block_k=8, window=window)
    g_flash = jax.grad(loss(flash_fn), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(ref_fn), argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        assert gf.shape == gr.shape
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), atol=1e-4, rtol=1e-4,
                                   err_msg=f"grad mismatch for {name}")


def test_unlike_head_sizes_leave_the_vmem_request_of_like_ones_alone():
    from trlx_tpu.ops import flash_attention as fa

    for D in (128, 256):
        assert fa._fwd_vmem_params(8192, D, 2, 512, 512, False) == fa._fwd_vmem_params(8192, D, 2, 512, 512, False, Dv=D)
        assert fa._bwd_vmem_params(8192, D, 2, 512, 512, False) == fa._bwd_vmem_params(8192, D, 2, 512, 512, False, Dv=D)
    assert fa._fwd_vmem_params(640, 192, 2, 640, 640, False, Dv=128) == {}  # a 640-slot row at 192 | 128 fits the default scope


# ---------------------------------------------------------------------------
# under a selection: each query's softmax over the keys it keeps
# ---------------------------------------------------------------------------


def _selection(B, T, keep, seed=0):
    """A random ``keep`` share of every query's keys, one set for all heads;
    query 3 of row 0 keeps none at all (a row the kernels must leave at 0)."""
    sel = np.random.RandomState(seed).rand(B, T, T) < keep
    sel[0, 3] = False
    return jnp.asarray(sel)


@pytest.mark.parametrize("left_pad", [0, 3])
@pytest.mark.parametrize("blocks", [(16, 16), (32, 16), (None, None)], ids=["16x16", "32x16", "chosen"])
@pytest.mark.parametrize("D,Dv", [(8, 8), (24, 16)], ids=["8_8", "24_16"])
def test_selection_forward_matches_reference(D, Dv, blocks, left_pad):
    T = 40  # not a multiple of the tiles: padded queries and keys keep nothing
    q, k, _, mask = _mk(T=T, S=T, D=D, left_pad=left_pad)
    v = _rand(jax.random.PRNGKey(9), 2, T, 2, Dv)
    sel = _selection(2, T, 0.4)
    out = flash_attention(q, k, v, mask, selection=sel, block_q=blocks[0], block_k=blocks[1], interpret=True)
    ref, _ = attention_reference(q, k, v, mask, selection=sel)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(out[0, 3]).max()) == 0.0
    # a selection that keeps every key is plain causal attention
    every = flash_attention(q, k, v, mask, selection=jnp.ones((2, T, T), bool), block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(every, flash_attention(q, k, v, mask, block_q=16, block_k=16, interpret=True), atol=2e-6)


@pytest.mark.parametrize("left_pad", [0, 3])
@pytest.mark.parametrize("blocks", [(16, 16), (32, 16)], ids=["16x16", "32x16"])
def test_selection_gradients_match_reference(blocks, left_pad):
    T = 48
    q, k, _, mask = _mk(T=T, S=T, D=24, left_pad=left_pad)
    v = _rand(jax.random.PRNGKey(9), 2, T, 2, 16)
    sel = _selection(2, T, 0.3, seed=1)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    kernel = lambda q, k, v: flash_attention(q, k, v, mask, selection=sel, block_q=blocks[0], block_k=blocks[1], interpret=True)
    oracle = lambda q, k, v: attention_reference(q, k, v, mask, selection=sel)[0]
    got = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("how", ["window", "alibi", "not_causal", "grouped_heads", "a_cache", "shape"])
def test_selection_refuses_what_its_kernels_do_not_mask(how):
    q, k, v, mask = _mk(T=16, S=16, H=2)
    sel, kw = jnp.ones((2, 16, 16), bool), {}
    if how == "window":
        kw["window"] = 4
    elif how == "alibi":
        kw.update(alibi_slopes=jnp.ones((2,)), q_positions=jnp.zeros((2, 16), jnp.int32), k_positions=jnp.zeros((2, 16), jnp.int32))
    elif how == "not_causal":
        kw["causal"] = False
    elif how == "grouped_heads":
        k, v = k[:, :, :1], v[:, :, :1]
    elif how == "a_cache":
        q, sel = q[:, :8], sel[:, :8]
    else:
        sel = sel[:, :, :8]
    with pytest.raises(ValueError, match="a selection runs causal MHA over the row's own keys"):
        flash_attention(q, k, v, mask, selection=sel, interpret=True, **kw)
