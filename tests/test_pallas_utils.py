"""Unit coverage for the shared Pallas plumbing (``ops/pallas_utils.py``)
factored out of the paged/flash/fused kernels (ISSUE 18 satellite): the
alignment, clamping, and bias-padding helpers every host wrapper now calls,
and the shared scalar-prefetch grid builder."""

import jax
import jax.numpy as jnp
import pytest

from trlx_tpu.ops import pallas_utils as pu


def test_align_rows_interpret_is_exact():
    for n in (1, 7, 8, 100, 128, 129):
        assert pu.align_rows(n, interpret=True) == n


def test_align_rows_hardware_rounds_to_lanes():
    assert pu.align_rows(1, interpret=False) == 128
    assert pu.align_rows(128, interpret=False) == 128
    assert pu.align_rows(129, interpret=False) == 256
    assert pu.align_rows(5, interpret=False, lanes=8) == 8


def test_clamp_block_table_bounds_and_dtype():
    tbl = jnp.array([[0, 3, 7, 12], [2, 99, 5, 7]], dtype=jnp.int64)
    out = pu.clamp_block_table(tbl, num_blocks=8)
    assert out.dtype == jnp.int32
    assert out.max() == 7
    # in-range ids pass through untouched
    assert (out[0, :3] == jnp.array([0, 3, 7])).all()


@pytest.mark.parametrize("ndim", [3, 4])
def test_pad_bias_to_casts_and_pads_last_axis(ndim):
    shape = (2, 1, 5) if ndim == 3 else (2, 1, 3, 5)
    bias = jnp.full(shape, -1e9, dtype=jnp.bfloat16)
    out = pu.pad_bias_to(bias, 8)
    assert out.dtype == jnp.float32
    assert out.shape == shape[:-1] + (8,)
    # original columns preserved (through the f32 cast), padding exactly 0
    assert jnp.array_equal(out[..., :5], bias.astype(jnp.float32))
    assert (out[..., 5:] == 0.0).all()
    # already-wide bias is cast but not sliced
    assert pu.pad_bias_to(bias, 4).shape == shape


def test_resolve_interpret_respects_explicit_knob():
    assert pu.resolve_interpret(True) is True
    assert pu.resolve_interpret(False) is False
    assert pu.resolve_interpret(None) == pu.default_interpret()


def test_paged_pool_grid_spec_drives_fetches_through_the_table():
    """The factored grid builder must behave exactly like the inline
    PrefetchScalarGridSpec it replaced: a trivial copy kernel assembling
    pool blocks through the table reproduces the gather view."""
    from jax.experimental import pallas as pl

    B, TB, bs, KV, D = 2, 3, 2, 1, 4
    NB = 5
    S = TB * bs
    pool = jnp.arange(NB * bs * KV * D, dtype=jnp.float32).reshape(
        NB, bs, KV, D
    )
    tbl = jnp.array([[4, 0, 2], [1, 1, 3]], dtype=jnp.int32)
    q = jnp.zeros((B, 1, D), dtype=jnp.float32)
    bias = jnp.zeros((B, 1, S), dtype=jnp.float32)

    def kernel(tbl_ref, q_ref, bias_ref, k_ref, v_ref, o_ref, k_buf, v_buf):
        j = pl.program_id(1)
        k_buf[pl.ds(j * bs, bs), :, :] = k_ref[0]

        @pl.when(j == TB - 1)
        def _finish():
            # fold the assembled row into the (1, 1, D) output so every
            # landed block is observable
            o_ref[...] = jnp.sum(k_buf[0:S, :, :], axis=(0, 1))[None, None, :]

    grid_spec = pu.paged_pool_grid_spec(
        batch=B,
        table_blocks=TB,
        block_size=bs,
        kv_heads=KV,
        head_dim=D,
        q_block=(1, 1, D),
        bias_block=(1, 1, S),
        out_block=(1, 1, D),
        scratch_rows=S,
        k_dtype=pool.dtype,
        v_dtype=pool.dtype,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, D), jnp.float32),
        interpret=True,
    )(tbl, q, bias, pool, pool)
    expect = pool[tbl].reshape(B, S, KV, D).sum(axis=(1, 2))
    assert jnp.array_equal(out[:, 0], expect)
