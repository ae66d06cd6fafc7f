"""``ops/grouped_matmul.py``: the megablox kernel under the Pallas interpreter
against ``jax.lax.ragged_dot`` in float32 at toy sizes, the tile rule at the
shapes the benchmark's MoE cell runs, and the choice of kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.ops import grouped_matmul as gm

K, N = 32, 48
TILES = (8, 16, 16)


@pytest.fixture(autouse=True)
def _no_global_mesh():
    """The choice of kernel reads the global mesh, and a trainer built by an
    earlier file in this process leaves its own behind."""
    from trlx_tpu.parallel.mesh import set_global_mesh

    set_global_mesh(None)
    yield
    set_global_mesh(None)

# name -> (rows, group sizes): the row tile is 8
CASES = {
    "even": (32, [8, 8, 8, 8]),
    "empty_group": (32, [12, 0, 0, 20]),
    "straddles_a_row_tile": (32, [3, 18, 5, 6]),
    "rows_past_the_last_group": (32, [5, 0, 9, 4]),
    "rows_not_a_multiple_of_the_tile": (27, [7, 11, 0, 9]),
    "short_and_padded": (13, [2, 1, 0, 3]),
}


def _operands(rows, sizes, seed=0):
    rs = np.random.RandomState(seed)
    lhs = jnp.asarray(rs.randn(rows, K), jnp.float32)
    rhs = jnp.asarray(rs.randn(len(sizes), K, N) * 0.3, jnp.float32)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


def _masked(fn, sizes):
    """``fn``'s result with the rows past the last group zeroed, as
    ``MoEMLP._dropless`` does."""

    def f(lhs, rhs):
        out = fn(lhs, rhs)
        return jnp.where((jnp.arange(out.shape[0]) < jnp.sum(sizes))[:, None], out, 0)

    return f


@pytest.mark.parametrize("what", ["forward", "grad_rows", "grad_kernels"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_gmm_interpreted_matches_ragged_dot(case, what):
    rows, sizes = CASES[case]
    lhs, rhs, sizes = _operands(rows, sizes)
    kernel = _masked(lambda a, b: gm._gmm(a, b, sizes, TILES, True), sizes)
    plain = _masked(lambda a, b: jax.lax.ragged_dot(a, b, sizes), sizes)
    if what == "forward":
        got, want = kernel(lhs, rhs), plain(lhs, rhs)
    else:
        weights = jnp.asarray(np.random.RandomState(1).randn(rows, N), jnp.float32)
        argnum = 0 if what == "grad_rows" else 1
        got = jax.grad(lambda a, b: jnp.sum(kernel(a, b) * weights), argnums=argnum)(lhs, rhs)
        want = jax.grad(lambda a, b: jnp.sum(plain(a, b) * weights), argnums=argnum)(lhs, rhs)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    if what == "grad_rows":  # rows in no group get no gradient, not what a kernel left
        assert not np.asarray(got)[int(jnp.sum(sizes)):].any()


def _vmem_bytes(tiles, itemsize):
    """The larger VMEM footprint of the forward ``gmm`` (also the rows'
    backward, with ``tk`` and ``tn`` swapped) and the kernels' ``tgmm``:
    double-buffered operand and result blocks plus the float32 accumulator."""
    tm, tk, tn = tiles
    return 2 * (tm * tk + tk * tn + tm * tn) * itemsize + 4 * max(tm * tn, tm * tk, tk * tn)


# (k, n): OLMoE's gate / up and down; Mixtral's; widths 1024 does not divide; unaligned toy widths
WIDTHS = [(2048, 1024), (1024, 2048), (4096, 14336), (14336, 4096), (1536, 768), (11008, 4096), (96, 40)]


@pytest.mark.parametrize("itemsize", [2, 4])
@pytest.mark.parametrize("widths", WIDTHS, ids=lambda w: "x".join(map(str, w)))
def test_tile_rule_divides_and_fits(widths, itemsize):
    k, n = widths
    tm, tk, tn = tiles = gm.gmm_tiles(k, n, itemsize)
    assert tm == gm.ROW_TILE == 128 and k % tk == 0 and n % tn == 0
    for dim, tile in ((k, tk), (n, tn)):  # lane-aligned, or the whole of an unaligned width
        assert tile % 128 == 0 or tile == dim
    assert max(tk, tn) <= (1024 if itemsize == 2 else 512) or (k, n) == (96, 40)
    assert _vmem_bytes(tiles, itemsize) <= 16 * 2**20  # Mosaic's default scoped VMEM
    if itemsize == 2:
        want = {(1536, 768): (768, 768), (11008, 4096): (256, 1024), (96, 40): (96, 40)}
        assert (tk, tn) == want.get((k, n), (1024, 1024))


def _traced_choice(rows, groups):
    """The choice as a jitted program sees it."""
    seen = []
    jax.make_jaxpr(lambda x: (seen.append(gm._short_groups_on_one_tpu(x, groups)), x)[1])(
        jax.ShapeDtypeStruct((rows, 8), jnp.float32)
    )
    return seen[0]


# (rows, groups) -> the kernel: OLMoE's decode step, prefill, train step and scoring forward in
# olmoe7b_grpo_decode (64 experts, top-8); Mixtral's decode step and a train minibatch (8, top-2)
@pytest.mark.parametrize(
    "rows,groups,short",
    [(512, 64, True), (65536, 64, False), (81920, 64, False), (327680, 64, False),
     (128, 8, True), (1024, 8, True), (4096, 8, False), (64 * 255, 64, True), (64 * 256, 64, False)],
)
def test_only_short_groups_take_the_kernel(monkeypatch, rows, groups, short):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _traced_choice(rows, groups) == short


def test_rows_visited_counts_every_group_tile_pair():
    tm = 8
    for rows, sizes in CASES.values():
        ends = np.cumsum(sizes)
        want = sum(
            len({r // tm for r in range(end - size, end)}) for size, end in zip(sizes, ends)
        ) * tm
        assert int(gm.gmm_rows_visited(jnp.asarray(sizes, jnp.int32), tm)) == want
    # a decode step of the cell: 512 assignments in 64 groups of 8 fill 6% of 128-row tiles
    even = jnp.full((64,), 8, jnp.int32)
    assert int(gm.gmm_rows_visited(even)) == 64 * 128
    assert int(gm.gmm_rows_visited(even, 512)) == 64 * 512  # the compiler's tile: 1.6%


def test_kernel_choice_follows_backend_mesh_and_trace(monkeypatch):
    from trlx_tpu.data.configs import ParallelConfig
    from trlx_tpu.parallel.mesh import make_mesh, set_global_mesh

    traced = lambda: _traced_choice(512, 64)  # short groups
    assert not traced()  # the CPU keeps ragged_dot
    lhs, rhs, sizes = _operands(32, [8, 8, 8, 8])
    text = str(jax.make_jaxpr(lambda a, b: gm.grouped_matmul(a, b, sizes))(lhs, rhs))
    assert "ragged_dot" in text and "pallas_call" not in text
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert traced()
    # eager (module.init): no kernel compiled for a dummy batch
    assert not gm._short_groups_on_one_tpu(jnp.zeros((512, 8)), 64)
    try:
        set_global_mesh(make_mesh(ParallelConfig(data=1), devices=jax.devices()[:1]))
        assert traced()  # a mesh of one device is one device
        set_global_mesh(make_mesh(ParallelConfig(data=1, fsdp=2, model=2), devices=jax.devices()[:4]))
        assert not traced()
    finally:
        set_global_mesh(None)


def test_moe_layer_is_the_same_through_either_kernel(monkeypatch):
    """The toy OLMoE expert layer (float32, 8 experts top-2) with padding
    tokens, through ``ragged_dot`` and, with the choice steered as one TPU
    device would answer it, through the interpreted kernel: the same output,
    gradients and statistics."""
    from trlx_tpu.models.transformer import MoEMLP, TransformerConfig

    cfg = TransformerConfig.olmoe("test", param_dtype=jnp.float32, dtype=jnp.float32)
    layer = MoEMLP(cfg)
    rs = np.random.RandomState(7)
    x = jnp.asarray(rs.randn(2, 12, cfg.hidden_size), jnp.float32)
    mask = jnp.ones((2, 12), jnp.int32).at[0, :5].set(0)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    params = jax.tree_util.tree_map(lambda a: a * 10, params)  # the init's 0.02 leaves y tiny

    def run(p, x):
        y, aux = layer.apply({"params": p}, x, mask)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), (y, aux)

    (_, (y0, aux0)), grads0 = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(params, x)
    monkeypatch.setattr(gm, "_short_groups_on_one_tpu", lambda lhs, groups: True)
    (_, (y1, aux1)), grads1 = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(params, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads1), jax.tree_util.tree_leaves(grads0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(aux1), np.asarray(aux0), rtol=1e-6)
    assert float(aux0[4]) == int(mask.sum()) * cfg.num_experts_per_tok


def test_a_kernel_that_leaves_the_padding_rows_gradient_unwritten_reaches_no_token(monkeypatch):
    """On the chip ``ragged_dot``'s backward leaves the gradient of the rows
    past the last group unwritten (NaN over memory that held NaN: PERF.md, PR
    40). Stand in for that with a ``ragged_dot`` whose backward poisons those
    rows: the layer's gradients, with padding tokens and with experts held
    elsewhere, are the sound kernel's."""
    import dataclasses

    from trlx_tpu.models.transformer import MoEMLP, TransformerConfig

    real = jax.lax.ragged_dot

    def leaves_the_tail(a, b, group_sizes, precision=None):
        @jax.custom_vjp
        def f(a, b):
            return real(a, b, group_sizes)

        def bwd(res, g):
            da, db = jax.vjp(lambda a, b: real(a, b, group_sizes), *res)[1](g)
            return jnp.where((jnp.arange(a.shape[0]) < jnp.sum(group_sizes))[:, None], da, jnp.nan), db

        f.defvjp(lambda a, b: (f(a, b), (a, b)), bwd)
        return f(a, b)

    cfg = TransformerConfig.olmoe("test", param_dtype=jnp.float32, dtype=jnp.float32)
    for cfg in (cfg, dataclasses.replace(cfg, moe_experts_held=2, moe_first_expert=1)):
        layer = MoEMLP(cfg)
        x = jnp.asarray(np.random.RandomState(7).randn(2, 12, cfg.hidden_size), jnp.float32)
        mask = jnp.ones((2, 12), jnp.int32).at[0, :5].set(0)
        params = jax.tree_util.tree_map(lambda a: a * 10, layer.init(jax.random.PRNGKey(0), x)["params"])
        loss = lambda p, x: jnp.sum(layer.apply({"params": p}, x, mask)[0] ** 2)
        want = jax.grad(loss, argnums=(0, 1))(params, x)
        with monkeypatch.context() as m:
            m.setattr(jax.lax, "ragged_dot", leaves_the_tail)
            got = jax.grad(loss, argnums=(0, 1))(params, x)
        for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
