"""The Pallas forward of the chunked gated delta rule
(``ops/delta_rule.py::kda_chunked`` at a head size of whole lanes), under the
interpreter on the CPU at the published head size of 128: against the
``jax.numpy`` form it replaces (``kda_chunked_reference``) and against the
token-by-token recurrence (``kda_step`` over the tokens), at the limits
``tests/test_kimi_linear.py`` holds the ``jax.numpy`` form to.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.ops import delta_rule
from trlx_tpu.ops.delta_rule import kda_chunked, kda_chunked_reference, kda_step, scan_takes_kernel

D = 128


def inputs(gate, T, B=2, H=2, pads=(7, 0), state=True, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(keys[0], (B, T, H, D)) / np.sqrt(D)
    k = jax.random.normal(keys[1], (B, T, H, D))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (B, T, H, D))
    if gate == "strongest":  # A 16, softplus input +6: about -96 a TOKEN, a chunk's decay far past -88
        g = -16.0 * jax.nn.softplus(6.0 + 0.1 * jax.random.normal(keys[3], (B, T, H, D)))
    else:  # the configuration's range: -0.001 to -1.6 a channel a token
        g = -jnp.exp(jax.random.uniform(keys[3], (B, T, H, D), minval=np.log(1e-3), maxval=np.log(1.6)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (B, T, H)))
    mask = jnp.stack([jnp.arange(T) >= p for p in pads]).astype(jnp.float32)  # padding in front
    s0 = jax.random.normal(keys[5], (B, H, D, D)) if state else None
    # a padded token is the caller's to mask, as `KDAMixer` does
    return q, k, v, g * mask[:, :, None, None], beta * mask[:, :, None], s0, mask


def token_by_token(q, k, v, g, beta, s0):
    def step(S, x):
        o, S = kda_step(S, *x)
        return S, o

    s0 = jnp.zeros((q.shape[0], q.shape[2], D, D)) if s0 is None else s0
    S, o = jax.lax.scan(step, s0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


def close(got, want, limit, floor=0.0):
    return float(jnp.abs(got - want).max()) < limit * max(float(jnp.abs(want).max()), floor)


def runs_the_kernel(fn, *args):
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("gate", ["configured", "strongest"])
@pytest.mark.parametrize(
    "T,state,chunk",
    [(150, True, 64), (150, False, 64), (128, True, 64), (70, True, 32), (24, True, 64)],
    ids=["last_chunk_not_whole", "from_zero", "whole_chunks", "chunks_of_32", "inside_one_chunk"],
)
def test_the_kernel_is_the_jax_numpy_form_and_the_recurrence(T, state, chunk, gate):
    """Outputs and final state, with padding in front of one row, from a
    non-zero state and from none; under the strongest gate the
    parameterisation gives, every value finite."""
    *args, mask = inputs(gate, T, state=state)
    m = mask[:, :, None, None]
    assert runs_the_kernel(lambda *a: kda_chunked(*a, chunk=chunk), *args)
    got_o, got_S = kda_chunked(*args, chunk=chunk)
    assert got_o.shape == args[2].shape and got_o.dtype == args[2].dtype and got_S.shape == (2, 2, D, D)
    assert bool(jnp.isfinite(got_o).all()) and bool(jnp.isfinite(got_S).all())
    for name, (want_o, want_S) in (("jax.numpy", kda_chunked_reference(*args, chunk=chunk)), ("recurrence", token_by_token(*args))):
        assert close(got_o * m, want_o * m, 2e-5), name
        assert close(got_S, want_S, 2e-5, floor=1e-3), name


@pytest.mark.parametrize("chunks", [48, 64])
def test_the_state_is_carried_over_a_rows_chunks_as_the_cell_runs_them(chunks):
    """One row of one head of 3072 and of 4096 tokens (the benchmark cell's
    prompt and its whole row): 48 and 64 chunks, one state in the kernel's
    scratch from the first to the last."""
    *args, _ = inputs("configured", 64 * chunks, B=1, H=1, pads=(11,), seed=chunks)
    got_o, got_S = jax.jit(kda_chunked)(*args)
    want_o, want_S = jax.jit(token_by_token)(*args)
    assert bool(jnp.isfinite(got_o).all()) and close(got_o, want_o, 2e-5) and close(got_S, want_S, 2e-5, floor=1e-3)


def test_a_bfloat16_v_gives_a_bfloat16_output_of_the_same_values():
    q, k, v, g, beta, s0, _ = inputs("configured", 100)
    got_o, got_S = kda_chunked(q, k, v.astype(jnp.bfloat16), g, beta, s0)
    want_o, want_S = kda_chunked_reference(q, k, v.astype(jnp.bfloat16), g, beta, s0)
    assert got_o.dtype == jnp.bfloat16
    assert close(got_o.astype(jnp.float32), want_o.astype(jnp.float32), 2.0**-7) and close(got_S, want_S, 2e-5)


@pytest.mark.parametrize("gate", ["configured", "strongest"])
def test_gradients_through_the_kernel_are_the_jax_numpy_forms(gate):
    """The backward rule differentiates the ``jax.numpy`` form from the kept
    inputs: the same gradients, to rounding, under ``jax.checkpoint`` as
    ``KDAMixer`` wraps a piece."""
    q, k, v, g, beta, s0, mask = inputs(gate, 100, H=1)
    m = mask[:, :, None, None]

    def grads(fn):
        def loss(q, k, v, g, beta, s0):
            o, S = jax.checkpoint(fn)(q, k, v, g, beta, s0)
            return jnp.sum((o * m) ** 2) + jnp.sum(S**2)

        return jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))(q, k, v, g, beta, s0)

    for name, got, want in zip("q k v g beta s0".split(), grads(kda_chunked), grads(kda_chunked_reference)):
        assert bool(jnp.isfinite(got).all()), name
        assert float(jnp.abs(got - want).max()) < 5e-5 * float(jnp.abs(want).max()) + 1e-9, name


@pytest.mark.parametrize("head,kernel", [(24, False), (128, True), (256, True), (64, False)])
def test_the_head_size_alone_chooses_the_form_and_the_gauge_says_which(head, kernel):
    """Whole lanes of key and value channels take the kernel, any other head
    size (the toy's 24) the ``jax.numpy`` form; ``learn/kda_scan_pallas`` is
    stamped from the same function."""
    from trlx_tpu.trainer.base import TPUBaseTrainer

    assert scan_takes_kernel(head, head) is kernel
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    args = (shape(1, 32, 1, head), shape(1, 32, 1, head), shape(1, 32, 1, head), shape(1, 32, 1, head), shape(1, 32, 1))
    assert runs_the_kernel(kda_chunked, *args) is kernel
    trainer = types.SimpleNamespace(tcfg=types.SimpleNamespace(kda_head_dim=head))
    _, stats = TPUBaseTrainer.with_router_aux(trainer, (0.0, {}), {"kda_stats": jnp.asarray([-3.0, 0.5])})
    assert stats["learn/kda_scan_pallas"] == float(kernel) and float(stats["learn/kda_log_decay_min"]) == -3.0


def test_a_chunk_that_is_not_whole_sub_blocks_is_refused():
    *args, _ = inputs("configured", 100)
    with pytest.raises(ValueError, match="whole sub-blocks"):
        kda_chunked(*args, chunk=40)


def test_the_kernels_event_carries_the_pieces_state_shape():
    """The benchmark finds the scan by result shape: the call's results are
    ``o`` in the mixer's layout and the final state ``[rows, heads, K, V]``."""
    *args, _ = inputs("configured", 64, B=2, H=2)
    jaxpr = str(jax.make_jaxpr(kda_chunked)(*args))
    assert delta_rule.KERNEL_NAME in jaxpr and "f32[2,2,128,128]" in jaxpr.replace(" ", "")
