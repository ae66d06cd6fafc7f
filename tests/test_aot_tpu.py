"""Compile every Pallas kernel flavor for a *described* TPU v5e.

The chip's compiler is installed in the CPU sandbox and compiles for a
device that is described, not attached (``jax.experimental.topologies``):
what Mosaic refuses here it refuses on the chip, at no chip time. One case
per ``KERNEL_PARITY`` flavor at GPT-2-small widths (12 heads x 64, vocab
50257, 64+40 tokens, KV block 16, bf16 activations as the trainer runs
them), plus flash attention as the model calls it under the four-device
mesh ``chip_smoke.py --chips 4`` runs on.

Nothing executes — this says nothing about results or times; the on-chip
comparison against the XLA references is the ``kernels`` phase of
``chip_smoke.py``. A flavor Mosaic still refuses is ``xfail(strict=True)``
with the compiler's words, so the PR that repairs it must flip it.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

# flavor -> the compiler's words, for flavors Mosaic still refuses: one
# table, kept where the chip run prints it
from chip_smoke import KERNELS_REFUSED as REFUSED
from trlx_tpu.analysis.kernels import KERNEL_PARITY

# GPT-2-small attention widths and chip_smoke.py's task shape
H, D, VOCAB = 12, 64, 50257
PROMPT, NEW = 64, 40
T = PROMPT + NEW
B = 8  # compile cost does not grow with the batch; widths are what matter
BLOCK = 16  # EngineConfig.kv_block_size default
TB = -(-T // BLOCK)
DT = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip (the next run warns and
    recompiles), so the cache stays off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, args, sharding):
    """Lower ``fn`` on shapes placed by ``sharding`` and compile it with
    the TPU compiler; the program must contain a Mosaic custom call."""
    sds = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), args
    )
    text = jax.jit(fn).lower(*sds).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _s(shape, dtype=DT):
    return jax.ShapeDtypeStruct(shape, dtype)


def _pool_args(q_shape, bias_shape):
    pool = _s((1 + B * TB, BLOCK, H, D))
    return _s(q_shape), pool, pool, _s((B, TB), jnp.int32), _s(bias_shape, jnp.float32)


def _flash_fwd():
    from trlx_tpu.ops.flash_attention import flash_attention

    x = _s((B, T, H, D))
    return (
        lambda q, k, v, m: flash_attention(q, k, v, m, interpret=False),
        (x, x, x, _s((B, T), jnp.float32)),
    )


def _flash_bwd():
    from trlx_tpu.ops.flash_attention import flash_attention_bwd_chunk

    x, row = _s((B, T, H, D)), _s((B, H, T), jnp.float32)
    return (
        lambda q, k, v, m, lse, delta, do: flash_attention_bwd_chunk(
            q, k, v, m, lse, delta, do, interpret=False
        ),
        (x, x, x, _s((B, T), jnp.float32), row, row, x),
    )


def _paged_decode():
    from trlx_tpu.ops.paged_attention import paged_attention_decode

    return (
        lambda *a: paged_attention_decode(*a, interpret=False),
        _pool_args((B, H, D), (B, 1, T)),
    )


def _paged_prefill():
    from trlx_tpu.ops.paged_prefill import paged_prefill_attention

    return (
        lambda *a: paged_prefill_attention(*a, interpret=False),
        _pool_args((B, PROMPT, H, D), (B, 1, PROMPT, T)),
    )


def _paged_verify():
    from trlx_tpu.ops.paged_attention import paged_verify_attention

    G = 4  # draft_gamma of the repo's speculative configs
    return (
        lambda *a: paged_verify_attention(*a, interpret=False),
        _pool_args((B, G + 1, H, D), (B, 1, G + 1, T)),
    )


def _fused_sample():
    from trlx_tpu.ops.paged_attention import fused_sample

    # the bench task's gen_kwargs: unfiltered sampling at temperature 1
    logits = _s((B, VOCAB), jnp.float32)
    return (
        lambda lg, g: fused_sample(
            lg, g, temperature=1.0, top_k=0, top_p=1.0, interpret=False
        ),
        (logits, logits),
    )


def _kda_scan():
    """The toy cannot reach this flavor (its heads are 24 wide): two heads of
    128 over 104 tokens, v in bf16 as the trainer runs it. The cell's own
    piece is ``test_the_chunked_delta_rule_compiles_at_the_kimi_cells_piece``."""
    from trlx_tpu.ops import delta_rule

    x, f32 = _s((B, T, 2, 128)), _s((B, T, 2, 128), jnp.float32)
    return (
        lambda *a: delta_rule._scan_with_xla_backward(*a, delta_rule.CHUNK, False),
        (f32, f32, x, f32, _s((B, T, 2), jnp.float32), _s((B, 2, 128, 128), jnp.float32)),
    )


_BUILDERS = {
    "flash-fwd": _flash_fwd,
    "flash-bwd": _flash_bwd,
    "paged-decode": _paged_decode,
    "paged-prefill": _paged_prefill,
    "paged-verify": _paged_verify,
    "fused-sample": _fused_sample,
    "kda-scan": _kda_scan,
}

def test_table_covers_the_registry():
    assert set(_BUILDERS) == {row[0] for row in KERNEL_PARITY}
    assert set(REFUSED) <= set(_BUILDERS)


@pytest.mark.parametrize(
    "flavor",
    [
        pytest.param(
            f,
            marks=pytest.mark.xfail(strict=True, reason=REFUSED[f]) if f in REFUSED else (),
        )
        for f in _BUILDERS
    ],
)
def test_kernel_compiles_for_v5e(topo, flavor):
    """Under the default matmul precision (whatever other test modules set
    at import) — and, for the flavors whose dots take bf16 operands, under
    ``highest`` too: Mosaic refuses an fp32 contraction on bf16 ("Bad lhs
    type"), so those kernels must not ask for one when a caller raises the
    default."""
    fn, args = _BUILDERS[flavor]()
    precisions = ("default", "highest") if flavor.startswith("paged-") else ("default",)
    for precision in precisions:
        with jax.default_matmul_precision(precision):
            _compile(fn, args, SingleDeviceSharding(topo.devices[0]))


def test_flash_under_four_device_mesh(topo, monkeypatch):
    """Flash attention as the model calls it (``Attention`` inside a
    ``CausalTransformer`` forward + backward) under ``fsdp=2, model=2``:
    Mosaic kernels cannot be partitioned by GSPMD, so the call must sit in a
    ``shard_map`` over the batch and head axes."""
    from trlx_tpu.models.transformer import CausalTransformer, TransformerConfig
    from trlx_tpu.parallel.mesh import MESH_AXES, set_global_mesh
    from trlx_tpu.parallel.sharding import param_shardings

    # steer the platform probes the way the chip would answer them
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    cfg = TransformerConfig(
        vocab_size=512, hidden_size=H * D, num_layers=1, num_heads=H,
        intermediate_size=4 * H * D, max_position_embeddings=T,
        dtype=DT,
    )
    model = CausalTransformer(cfg)
    mesh = Mesh(
        np.asarray(topo.devices).reshape(1, 1, 2, 2, 1, 1), MESH_AXES
    )
    ids = jnp.zeros((B, T), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids[:1, :8])
    )["params"]
    shardings = param_shardings(params, mesh)
    params = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        params, shardings,
    )
    batch = NamedSharding(mesh, P(("data", "fsdp")))
    ids_s = jax.ShapeDtypeStruct(ids.shape, ids.dtype, sharding=batch)

    def loss(p, x):
        out = model.apply({"params": p}, x, attention_mask=jnp.ones_like(x))
        return out["logits"].astype(jnp.float32).mean()

    set_global_mesh(mesh)
    try:
        with jax.default_matmul_precision("default"):
            text = jax.jit(jax.value_and_grad(loss)).lower(params, ids_s).compile().as_text()
    finally:
        set_global_mesh(None)
    assert "tpu_custom_call" in text
    # heads are sharded over `model`: each device runs the kernel on 6 of 12
    assert f"{B // 2},{H // 2}," in text.replace(" ", "")


def test_flash_kernels_keep_their_names_in_the_compiled_program(topo):
    """A device trace's ``XLA Ops`` event is the instruction's text, and the
    benchmark's ``flash_fwd_device_ms`` / ``flash_bwd_device_ms`` match on
    its name. The TPU compiler names a custom call after the innermost
    named scope: ``pallas_call(name=...)`` must win over the enclosing flax
    scope (``%attn.N`` before the kernels had names), in the forward and in
    the transposed backward."""
    from trlx_tpu.ops import flash_attention as fa

    def loss(q, k, v, m):
        with jax.named_scope("attn"):
            return fa.flash_attention(q, k, v, m, interpret=False).astype(jnp.float32).sum()

    x = _s((B, T, H, D))
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), (x, x, x, _s((B, T), jnp.float32)),
                    SingleDeviceSharding(topo.devices[0]))
    _assert_the_benchmark_finds_both_kernels(text)


def test_a_stored_programs_lowering_does_not_depend_on_who_called_it(topo):
    """The program store's guard compares the StableHLO text a program was
    compiled from with a fresh lowering's (``utils/programs.py::verify``). A
    Mosaic kernel's serialized body carries its locations, and by default
    frames of the jitted function's CALLERS among them, so the same program
    lowered under a collection and under the guard differs in text (cell 10 on
    the chip, PR 50) and holds the checkout's path. The store lowers without
    the call stack: the text is then the program's alone."""
    import base64
    import re

    from trlx_tpu.ops import flash_attention as fa
    from trlx_tpu.utils import programs

    def loss(q, k, v, m):
        return fa.flash_attention(q, k, v, m, interpret=False).astype(jnp.float32).sum()

    x, one = _s((B, T, H, D)), SingleDeviceSharding(topo.devices[0])
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        (x, x, x, _s((B, T), jnp.float32)))

    def lower(depth):
        if depth:
            return lower(depth - 1)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args).as_text()

    def bodies(text):
        return [base64.b64decode(b) for b in re.findall(r'body\\22: \\22([A-Za-z0-9+/=]*)', text)]

    assert lower(0) != lower(3)  # the control: the callers are in the kernels' locations
    assert any(b".py" in body for body in bodies(lower(0)))
    with programs._no_call_stack():
        shallow, deep = lower(0), lower(3)
    assert shallow == deep and len(bodies(shallow)) >= 2
    assert not any(b".py" in body or b"/" + b"root" in body for body in bodies(shallow))
    assert jax.config.jax_traceback_in_locations_limit != 0  # and the limit is back


def test_selected_flash_kernels_compile_and_keep_the_names(topo):
    """Under a selection (``flash_attention(..., selection=)``) the forward
    and the fused backward are kernels of their own with an int8 tile of the
    selection beside the key mask: at cell 8's shape (one row of 8192 slots,
    64 heads of 256, a 512 x 512 tile, a head's K and V and a query block's
    8192 selection columns in VMEM) Mosaic takes both, and they carry the
    names the benchmark's flash metrics match."""
    from trlx_tpu.ops import flash_attention as fa

    def loss(q, k, v, m, sel):
        with jax.named_scope("attn"):
            return fa.flash_attention(q, k, v, m, selection=sel, interpret=False).astype(jnp.float32).sum()

    x = _s((1, 8192, 64, 256))
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                    (x, x, x, _s((1, 8192), jnp.float32), _s((1, 8192, 8192), jnp.bool_)),
                    SingleDeviceSharding(topo.devices[0]))
    _assert_the_benchmark_finds_both_kernels(text)
    assert "s8[1,8192,8192]" in text.replace(" ", "")  # the selection reaches the kernels a byte a pair


def test_block_selected_flash_kernels_compile_and_keep_the_names(topo):
    """Under a selection by blocks of keys (``selection_block=64``, one set a
    KV head under GQA 32 : 2) at the MiniCPM-SALA cell's shape, one row of
    16384 slots and heads of 128: the selection reaches the kernels as one
    bf16 a (query, block), 256 lanes a query, and a tile's mask is widened
    from it on the MXU; Mosaic takes both kernels under the usual names."""
    from trlx_tpu.ops import flash_attention as fa

    def loss(q, k, v, m, sel):
        with jax.named_scope("attn"):
            return fa.flash_attention(q, k, v, m, selection=sel, selection_block=64, interpret=False).astype(jnp.float32).sum()

    q, kv = _s((1, 16384, 32, 128)), _s((1, 16384, 2, 128))
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                    (q, kv, kv, _s((1, 16384), jnp.float32), _s((1, 2, 16384, 256), jnp.bool_)),
                    SingleDeviceSharding(topo.devices[0]))
    _assert_the_benchmark_finds_both_kernels(text)
    assert "bf16[1,2,16384,256]" in text.replace(" ", "")  # 1/64 of a byte a pair... two bytes a block


def _metric_pattern(name):
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "layer_metrics", f"{name}.json")) as f:
        return json.load(f)["pattern"]


def _executed(text):
    """The instructions of a compiled module that run as events of their own:
    those of the computations that hold fusions (a fused computation holds
    none), without the ones that only name a value."""
    import re

    out = []
    for block in re.split(r"\n(?=(?:ENTRY )?%[^\n]*\{\n)", text):
        if " fusion(" in block:
            lines = [l.strip().removeprefix("ROOT ") for l in block.split("\n")[1:]]
            out += [l for l in lines if l.startswith("%") and not re.search(
                r" (?:get-tuple-element|bitcast|constant|parameter|tuple|while|iota)\(", l)]
    return out


def test_the_chunked_delta_rule_compiles_at_the_kimi_cells_piece_and_the_benchmark_finds_it(topo, monkeypatch):
    """``ops/delta_rule.py`` at the Kimi-Linear cell's shapes: the chunked form
    on a piece of 2 rows of 4096 tokens, 32 heads of 128, forward (the Pallas
    kernel) and, run again under ``jax.checkpoint``, backward (the
    ``jax.numpy`` form differentiated), inside the chip's memory with room for
    what the program holds; the prefill's piece of 3072 tokens from a stored
    state, forward alone; and the one-token step on 32 rows.
    ``kda_scan_device_ms`` reads its events by result shape and
    ``kda_step_device_ms`` by the state's shape in a fusion's text: the scan's
    pattern finds the kernel's call (by the piece's final state among its
    results) and the bulk of the backward's instructions, and neither pattern
    any of the other's program."""
    import re

    from trlx_tpu.ops.delta_rule import KERNEL_NAME, kda_chunked, kda_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the choice of Mosaic over the interpreter, as the chip answers it
    one = SingleDeviceSharding(topo.devices[0])
    place = lambda args: jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), args)

    def loss(q, k, v, g, beta):
        o, state = jax.checkpoint(kda_chunked)(q, k, v, g, beta)
        return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(state)

    f32 = lambda *shape: _s(shape, jnp.float32)
    scan_args = (f32(2, 4096, 32, 128), f32(2, 4096, 32, 128), _s((2, 4096, 32, 128)), f32(2, 4096, 32, 128), f32(2, 4096, 32))
    scan = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(*place(scan_args)).compile()
    assert scan.memory_analysis().temp_size_in_bytes < 5 * 2**30
    prefill_args = tuple(_s((2, 3072) + a.shape[2:], a.dtype) for a in scan_args) + (f32(2, 32, 128, 128),)
    prefill = jax.jit(kda_chunked).lower(*place(prefill_args)).compile()
    assert prefill.memory_analysis().temp_size_in_bytes < 2**30  # no float32 chunk arrays: the layout copies of q, k, v, g at most
    step_args = (f32(32, 32, 128, 128), f32(32, 32, 128), f32(32, 32, 128), _s((32, 32, 128)), f32(32, 32, 128), f32(32, 32))
    step = jax.jit(kda_step).lower(*place(step_args)).compile()
    scan_rx, step_rx = re.compile(_metric_pattern("kda_scan_device_ms")), re.compile(_metric_pattern("kda_step_device_ms"))
    for program in (scan, prefill):  # one call a pass, named, and the metric's pattern finds it
        calls = [l.strip().removeprefix("ROOT ") for l in program.as_text().splitlines() if 'custom_call_target="tpu_custom_call"' in l]
        assert len(calls) == 1 and calls[0].startswith(f"%{KERNEL_NAME}") and scan_rx.search(calls[0]) and not step_rx.search(calls[0]), calls
    scan_ops, step_ops = _executed(scan.as_text()), _executed(step.as_text())
    assert sum(bool(scan_rx.search(l)) for l in scan_ops) > 0.75 * len(scan_ops) > 100
    assert sum(bool(step_rx.search(l)) for l in step_ops) >= 1
    assert not any(step_rx.search(l) for l in scan_ops) and not any(scan_rx.search(l) for l in step_ops)


def _assert_the_benchmark_finds_both_kernels(text):
    """Two Mosaic calls, and each of ``flash_fwd_device_ms`` /
    ``flash_bwd_device_ms``'s patterns matches exactly its own."""
    import json
    import re

    from trlx_tpu.ops import flash_attention as fa

    calls = [l.strip().removeprefix("ROOT ") for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    assert len(calls) == 2  # the forward and the fused backward
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for metric, kernel in (("flash_fwd_device_ms", fa.FWD_KERNEL_NAME),
                           ("flash_bwd_device_ms", fa.BWD_KERNEL_NAME)):
        with open(os.path.join(root, "chipbench", "layer_metrics", f"{metric}.json")) as f:
            pattern = json.load(f)["pattern"]
        hits = [c for c in calls if re.search(pattern, c)]
        assert len(hits) == 1 and hits[0].startswith(f"%{kernel}"), (metric, calls)


@pytest.mark.parametrize(
    "shape,window",
    [((1, 8192, 28, 4, 128), 4096), ((1, 8192, 28, 4, 128), None), ((8, 1024, 16, 16, 256), None), ((16, 640, 32, 8, 128), None),
     ((4, 1152, 32, 8, 128), None), ((8, 1152, 32, 8, 64), None)],
    ids=["window_4096", "global", "gptj_1024x256", "mistral_640x128", "not_a_multiple_of_512", "lfm2_1152x64"],
)
def test_flash_forward_and_backward_compile_at_the_chosen_tile(topo, shape, window):
    """The learners' attention shapes at the tile the kernel chooses for
    them (``choose_blocks``; no tile is passed, as the model passes none):
    ``smallthinker21b_grpo_ctx8k``'s one row of 8192 slots, 28 query heads
    over 4 key/value heads of 128, under its window and without (512 x 512);
    GPT-J's eight rows of 1024 at head size 256 (512 x 512); sixteen rows of
    640 at 32 / 8 heads of 128 (one 640 x 640 tile); a long row that 512 does
    not divide (1152 slots: 384 x 384, no slot of padding), and
    ``lfm2_8b_grpo_reason_r128``'s minibatch of eight such rows at 32 / 8 heads
    of 64, half a vector register's lanes a head. Both kernels must lower
    for the chip, keep the names the benchmark's ``flash_fwd_device_ms`` /
    ``flash_bwd_device_ms`` match on, and fit the VMEM they ask for: the
    fused backward keeps whole-sequence q, do, dq, lse and delta in VMEM
    across its k-block steps (32 MiB double-buffered at 8192: Mosaic's
    default 16 MiB scope refuses it, "Ran out of memory in memory space
    vmem") and a tile's working set grows with the tile, so past the scope
    each kernel asks for its own limit (``_vmem_params``); at 128 x 128 on a
    row of 1024 slots it asks for nothing and is the program it was."""
    from trlx_tpu.ops import flash_attention as fa

    B, T, H, KV, D = shape
    block_q, block_k = fa.choose_blocks(T, T)
    assert (block_q, block_k) == {640: (640, 640), 1152: (384, 384)}.get(T, (512, 512))
    assert fa._bwd_vmem_params(1024, 128, 2, 128, 128, False) == {} == fa._bwd_vmem_params(1024, 256, 2, 128, 128, False)
    assert fa._fwd_vmem_params(1024, 256, 2, 128, 128, False) == {}
    assert fa._bwd_vmem_params(8192, 128, 2, 512, 512, True) == {}  # the interpreter has no VMEM
    limit = fa._bwd_vmem_params(T, D, 2, block_q, block_k, False)
    if T == 8192:
        # 32 MiB resident and twice a 512 x 512 tile's 9 MiB
        assert limit["compiler_params"].vmem_limit_bytes == 50 * 2**20
    else:
        assert limit == {} or limit["compiler_params"].vmem_limit_bytes < 64 * 2**20

    def loss(q, k, v, m):
        with jax.named_scope("attn"):  # the model's flax scope
            return fa.flash_attention(q, k, v, m, window=window, interpret=False).astype(jnp.float32).sum()

    q, kv = _s((B, T, H, D)), _s((B, T, KV, D))
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), (q, kv, kv, _s((B, T), jnp.float32)),
                    SingleDeviceSharding(topo.devices[0]))
    _assert_the_benchmark_finds_both_kernels(text)


@pytest.mark.parametrize("rows,width", [(8, 640), (64, 128)], ids=["train_8x640", "prefill_64x128"])
def test_flash_compiles_with_unlike_head_sizes_at_latent_attention_widths(topo, rows, width):
    """Latent attention's expanded form: 128 heads whose q and k are 192 wide
    and whose v is 128, a train step's minibatch and the sampler's prefill of
    ``pangu718b_ppo_decode``. Nothing is padded to the larger size: the
    kernels take a 192-lane q/k block beside a 128-lane v/o block, keep their
    names, and fit the VMEM they ask for."""
    from trlx_tpu.ops import flash_attention as fa

    H, D, Dv = 128, 192, 128
    limit = fa._bwd_vmem_params(width, D, 2, *fa.choose_blocks(width, width), False, Dv=Dv)
    assert limit == {} or limit["compiler_params"].vmem_limit_bytes < 64 * 2**20

    def loss(q, k, v, m):
        with jax.named_scope("attn"):
            out = fa.flash_attention(q, k, v, m, interpret=False)
            assert out.shape == (rows, width, H, Dv)
            return out.astype(jnp.float32).sum()

    qk = _s((rows, width, H, D))
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), (qk, qk, _s((rows, width, H, Dv)), _s((rows, width), jnp.float32)),
                    SingleDeviceSharding(topo.devices[0]))
    _assert_the_benchmark_finds_both_kernels(text)
    assert f"bf16[{rows},{H},{width},{Dv}]" in text.replace(" ", "")


@pytest.mark.parametrize("width,heads,D,window,counted", [(8192, 64, 256, 513, 2), (7168, 64, 256, 513, 2), (8192, 128, 192, None, 0)],
                         ids=["train_row_window_layer", "prefill_row_window_layer", "train_row_full_layer"])
def test_flash_compiles_with_unlike_head_sizes_under_a_window_and_the_benchmark_tells_the_layers(topo, width, heads, D, window, counted):
    """``dots3note_ppo_ctx8k``'s two kinds of latent layer, one row a piece: a
    window layer's 64 heads of q/k 256 and v 128 under ``window=513``, forward
    and backward, and a full layer's 128 heads of 192 / 128. Both kernels keep
    their names; ``window_latent_pass_device_ms``'s pattern finds the window
    layer's two calls (by the first result's 64 heads) and none of a full
    layer's."""
    import json
    import re

    from trlx_tpu.ops import flash_attention as fa

    Dv = 128

    def loss(q, k, v, m):
        with jax.named_scope("attn"):
            out = fa.flash_attention(q, k, v, m, interpret=False, window=window)
            assert out.shape == (1, width, heads, Dv)
            return out.astype(jnp.float32).sum()

    qk = _s((1, width, heads, D))
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), (qk, qk, _s((1, width, heads, Dv)), _s((1, width), jnp.float32)),
                    SingleDeviceSharding(topo.devices[0]))
    _assert_the_benchmark_finds_both_kernels(text)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "layer_metrics", "window_latent_pass_device_ms.json")) as f:
        pattern = json.load(f)["pattern"]
    calls = [l.strip().removeprefix("ROOT ") for l in text.splitlines() if 'custom_call_target="tpu_custom_call"' in l]
    assert sum(bool(re.search(pattern, c)) for c in calls) == counted, calls


def test_dense_decode_step_builds_no_repeated_kv(topo, monkeypatch, clean_trace_state):
    """Cached single-token decoding runs the dense einsum branch of
    ``Attention``. At the attention shapes of ``mistral7b_grpo_decode``
    (64 rows, 640 cache slots, 32 query / 8 KV heads of 128, bf16; one layer
    of the ``mistral`` preset) the optimised program must hold no array as
    large as K or V repeated to every query head (``B*S*H*D`` elements), in
    the decode loop's body or outside it, whatever the compiler calls its
    shape. A count from shapes: it says nothing about time."""
    import re

    from trlx_tpu.models.transformer import CausalTransformer, TransformerConfig, make_kv_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, prompt, slots = 64, 128, 640
    cfg = TransformerConfig.mistral("7b", num_layers=1, dtype=DT, param_dtype=DT)
    model = CausalTransformer(cfg)
    one_chip = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    params = place(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"])
    cache = place(jax.eval_shape(lambda: make_kv_cache(cfg, rows, slots)))

    def decode(params, token, slot_mask, cache):
        def step(i, carry):
            token, cache = carry
            out = model.apply(
                {"params": params}, token, attention_mask=slot_mask,
                positions=jnp.full((rows, 1), prompt + i), cache=cache, cache_index=prompt + i,
            )
            return jnp.argmax(out["logits"][:, -1], axis=-1).astype(jnp.int32)[:, None], out["cache"]

        return jax.lax.fori_loop(0, 4, step, (token, cache))

    text = jax.jit(decode).lower(
        params, place(_s((rows, 1), jnp.int32)), place(_s((rows, slots), jnp.int32)), cache
    ).compile().as_text()
    assert " while(" in text
    sizes = {
        shape: int(np.prod([int(d) for d in shape.split(",")]))
        for shape in re.findall(r"\b[a-z]+\d*\[(\d+(?:,\d+)*)\]", text)
    }
    assert max(sizes.values()) >= rows * slots * 8 * 128  # the cache itself is there
    repeated = rows * slots * cfg.num_heads * cfg.dims_per_head
    assert not {s: n for s, n in sizes.items() if n >= repeated}


@pytest.mark.parametrize("head_dim", [64, 128])
def test_the_decode_loop_keeps_the_cache_channels_minor(topo, monkeypatch, clean_trace_state, head_dim):
    """The regression PR 61 removed, held where tier-1 sees it. At the
    attention shapes of ``lfm2_8b_grpo_reason_r128`` (128 rows of 1152 slots,
    32 query / 8 KV heads, bf16, the sampler's extents; one llama layer) the
    decode loop's carried ``k`` and ``v`` must not have the SLOT axis as
    their minor one: at a head of 64 the compiler turned ``[128, 1152, 8,
    64]`` slot-minor (``{1,3,2,0...}``) to suit the score product, and a
    step's write of one slot became 4096 strided read-modify-writes, 208 us
    where the rows are 131 KB (PERF.md section 6, PR 61). Two heads side by
    side in a 128-lane row (``ops/cache_layout.py::lane_heads``) keep the
    channels minor, as a head of 128 always had them. Read off the compiled
    program's text; nothing is timed. (``clean_trace_state``: a mesh of CPU
    devices an earlier test of the worker left behind would pin the embedding.)"""
    import re

    from trlx_tpu.models.transformer import CausalTransformer, TransformerConfig, make_kv_cache
    from trlx_tpu.ops.sampling import kv_extents

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, prompt, new = 128, 128, 1024
    slots = prompt + new
    cfg = TransformerConfig.llama("7b", num_layers=1, hidden_size=2048, num_heads=32, num_kv_heads=8, head_dim=head_dim,
                                  intermediate_size=2048, vocab_size=4096, dtype=DT, param_dtype=DT)
    model = CausalTransformer(cfg)
    one_chip = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    params = place(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"])
    cache = place(jax.eval_shape(lambda: make_kv_cache(cfg, rows, slots)))
    leaf = cache[0]["k"].shape
    assert leaf == ((rows, slots, 4, 128) if head_dim == 64 else (rows, slots, 8, 128))
    extents = kv_extents(prompt, new)
    assert len(extents) > 1

    def decode(params, token, slot_mask, cache, steps):
        def step(i, carry):
            token, cache = carry
            out = model.apply(
                {"params": params}, token, attention_mask=slot_mask, positions=jnp.full((rows, 1), prompt + i),
                cache=cache, cache_index=prompt + i, kv_extents=extents,
            )
            return jnp.argmax(out["logits"][:, -1], axis=-1).astype(jnp.int32)[:, None], out["cache"]

        return jax.lax.fori_loop(0, steps, step, (token, cache))

    text = jax.jit(decode).lower(
        params, place(_s((rows, 1), jnp.int32)), place(_s((rows, slots), jnp.int32)), cache, place(_s((), jnp.int32))
    ).compile().as_text()
    loops = [line for line in text.splitlines() if " while(" in line]
    assert loops
    shape = ",".join(str(n) for n in leaf)
    carried = [layout for line in loops for layout in re.findall(rf"bf16\[{shape}\]\{{([^}}]*)\}}", line)]
    assert len(carried) >= 2, loops  # k and v, in the loop's result (and its operand)
    assert not [layout for layout in carried if layout.startswith("1,")], carried
    writes = [line for line in text.splitlines() if " dynamic-update-slice(" in line and f"bf16[{shape}]" in line]
    assert len(writes) >= 2 and not [w for w in writes if re.search(rf"= bf16\[{shape}\]\{{1,", w)], writes


@pytest.mark.parametrize(
    "tokens,grad,mesh_shape",
    [((64, 1), False, None), ((2, 640), True, None), ((16, 640), True, None), ((64, 1), False, (2, 2))],
    ids=["decode_step", "short_train_step", "train_step", "fsdp2_model2"],
)
def test_dropless_experts_are_grouped_kernels_at_olmoe_widths(topo, monkeypatch, tokens, grad, mesh_shape):
    """One OLMoE expert layer (64 experts of 1024 on hidden 2048, top-8,
    bf16) as ``olmoe7b_grpo_decode`` runs it: a decode step of 64 rows, and
    a train step's 16 x 640 tokens forward and backward. On one chip the
    decode step's three matmuls (512 rows in 64 groups) are the megablox
    kernel (``ops/grouped_matmul.py``), and so is a train step short enough
    for groups under 256 rows (2 x 640 tokens), with its backward: the
    compiler names the forward's calls ``%gmm.N`` and, compiled alone, the
    backward's after jax's transformation stack
    (``%transpose_jvp_jit_gmm___.N`` for the rows, ``...tgmm...`` for the
    kernels), so only ``gmm`` / ``tgmm`` inside the name is held. The cell's
    train step has long groups and keeps ``jax.lax.ragged_dot``, which the
    compiler makes its own Mosaic kernels ``%ragged-dot-none.N``; so does
    every mesh of several devices (``fsdp=2, model=2`` here), where GSPMD can
    place ``ragged_dot`` and a Pallas call would need a ``shard_map``. Either
    way the experts must not expand to one dense matmul per expert: the
    program's FLOPs are those of the B*T*k assignments, not of ``E`` times
    them. Counts from the compiler; it says nothing about time."""
    import re

    from trlx_tpu.models.transformer import MoEMLP, TransformerConfig
    from trlx_tpu.ops.grouped_matmul import SHORT_GROUP
    from trlx_tpu.parallel.mesh import MESH_AXES, set_global_mesh
    from trlx_tpu.parallel.sharding import param_shardings

    # steer the platform probe the way the chip would answer it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = TransformerConfig.olmoe("1b-7b", dtype=DT, param_dtype=DT)
    layer = MoEMLP(cfg)
    x = _s(tokens + (cfg.hidden_size,))
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, cfg.hidden_size), DT)))["params"]
    place = lambda tree, shardings: jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, shardings
    )
    if mesh_shape is None:
        mesh = None
        one_chip = SingleDeviceSharding(topo.devices[0])
        params = place(params, jax.tree_util.tree_map(lambda _: one_chip, params))
        x = place(x, one_chip)
    else:
        mesh = Mesh(np.asarray(topo.devices).reshape((1, 1) + mesh_shape + (1, 1)), MESH_AXES)
        # the layer's own parameter rules, under the name a Block gives it
        params = place({"mlp": params}, param_shardings({"mlp": params}, mesh))["mlp"]
        x = place(x, NamedSharding(mesh, P(("data", "fsdp"))))

    def fwd(p, x):
        y, aux = layer.apply({"params": p}, x)
        return jnp.sum(y.astype(jnp.float32)) + aux[0]

    assignments = tokens[0] * tokens[1] * cfg.num_experts_per_tok
    short = mesh is None and assignments < SHORT_GROUP * cfg.num_experts
    set_global_mesh(mesh)
    try:
        # test modules that share this process raise the default, which Mosaic
        # refuses for bf16: ragged_dot has to pin its own precision; jax's
        # megablox kernels take the ambient one
        with jax.default_matmul_precision("default" if short else "highest"):
            compiled = jax.jit(jax.grad(fwd, argnums=(0, 1)) if grad else fwd).lower(params, x).compile()
    finally:
        set_global_mesh(None)
    calls = [l.strip().removeprefix("ROOT ") for l in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    named = lambda pattern: [c for c in calls if re.search(pattern, c.split(" = ")[0])]
    # gate, up, down; the backward adds one for the rows and one for the kernel of each
    if short:
        assert len(named(r"(?<!t)gmm")) == (6 if grad else 3), [c[:60] for c in calls]
        assert len(named(r"tgmm")) == (3 if grad else 0), [c[:60] for c in calls]
        assert not named(r"ragged-dot")
    else:
        assert len(named(r"^%ragged-dot-(?!metadata)")) == (9 if grad else 3), [c[:60] for c in calls]
        assert not named(r"gmm")
    # under the mesh too these are a device's FLOPs: the partitioner gathers
    # rows and kernels and every device computes the whole grouped matmul
    matmul = 2 * assignments * cfg.hidden_size * cfg.intermediate_size
    flops = compiled.cost_analysis()["flops"]
    assert 3 * matmul * (3 if grad else 1) <= flops < 1.5 * 3 * matmul * (3 if grad else 1)


@pytest.mark.parametrize("frozen", [True, False], ids=["frozen_kernels", "trained_kernels"])
def test_a_held_share_is_one_window_body_a_direction_and_a_frozen_kernel_has_no_gradient(topo, monkeypatch, frozen):
    """One OLMoE-width expert layer that holds 8 of its 64 experts, 4 x 1024
    tokens forward and backward: 32768 assignments in windows of 8192 sorted
    rows (``held_row_bound``). The compiled value and gradient have two ``while`` loops
    and no ``conditional`` (one body a direction: the code the chip holds),
    every grouped matmul over rows has 8192 of them, and where the kernels
    reach the layer through ``stop_gradient`` (the train step's frozen-leaf
    rule) no grouped matmul has a ``[held, d, f]`` result: their gradient is
    only ever added to itself in the backward loop's carry, and THIS compiler
    takes the carry and the kernel that feeds it out. With the kernels
    trained the same walk finds the three."""
    import re

    from trlx_tpu.models.transformer import MoEMLP, TransformerConfig, held_row_bound

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = TransformerConfig.olmoe("1b-7b", dtype=DT, param_dtype=DT, moe_experts_held=8, moe_first_expert=8)
    layer = MoEMLP(cfg)
    tokens, d, f = (4, 1024), cfg.hidden_size, cfg.intermediate_size
    bound = held_row_bound(tokens[0] * tokens[1] * cfg.num_experts_per_tok, 8, cfg.num_experts)
    assert bound == 8192
    one_chip = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = place(jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, d), DT)))["params"])
    assert params["w_up"].shape == (8, d, f)

    def loss(p, x):
        if frozen:
            p = {k: v if k == "router" else jax.lax.stop_gradient(v) for k, v in p.items()}
        y, aux = layer.apply({"params": p}, x)
        return jnp.sum(y.astype(jnp.float32)) + aux[0]

    with jax.default_matmul_precision("highest"):
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(params, place(_s(tokens + (d,)))).compile().as_text()
    assert (len(re.findall(r" while\(", text)), len(re.findall(r" conditional\(", text))) == (2, 0)
    results = re.findall(r"%ragged-dot-(?!metadata)[\w.-]* = bf16\[([0-9,]+)\]", text)
    over_rows = [r for r in results if r in (f"{bound},{f}", f"{bound},{d}")]
    of_kernels = [r for r in results if r in (f"8,{d},{f}", f"8,{f},{d}")]
    # gate, up, down forward; again where the backward differentiates the window; their rows' gradients
    assert len(over_rows) == 9 and len(over_rows) + len(of_kernels) == len(results), results
    assert len(of_kernels) == (0 if frozen else 3), results


@pytest.mark.parametrize("family,held,tokens,K,d", [("smallthinker", 16, (8, 1024), 6, 2560), ("lfm2", 8, (8, 1152), 4, 2048)],
                         ids=["k6_d2560", "k4_d2048"])
def test_a_held_share_never_views_its_row_buffers_by_choice(topo, monkeypatch, family, held, tokens, K, d):
    """The windowed layer at a train step of cell 6 (8192 tokens, 6 of 16
    held of 64, hidden 2560) and of cell 13 (9216 tokens, 4 of 8 held of 32,
    hidden 2048), value and gradient. A ``[tokens x K, d]`` buffer viewed
    ``[tokens, K, d]`` is free at ``K = 8`` alone; at 6 and 4 THIS compiler
    makes the view a copy (``reshape bf16[8192,6,2560]{..T(8,128)(2,1)}``,
    ``bf16[9216,4,2048]{..T(4,128)(2,1)}``: PR 61's tree holds five a
    layer). ``held_rows`` gathers the rows choice-major, by the ``K`` index
    columns laid end to end, and sums a token's ``K`` whole slices, so the
    compiled program has no ``[tokens, K, d]`` array at all, and the only
    ``[tokens x K, d]`` results outside fusions' bodies are the two rooms
    (``empty``), the loops' carries, the windows' own writes into them and
    the one gather a direction that reads them: no ``reshape``, ``copy`` or
    ``transpose`` of one."""
    import re

    from trlx_tpu.models.transformer import MoEMLP, TransformerConfig, held_row_bound

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = getattr(TransformerConfig, family)(dtype=DT, param_dtype=DT, moe_experts_held=held, moe_first_expert=0)
    N = tokens[0] * tokens[1]
    assert (cfg.num_experts_per_tok, cfg.hidden_size) == (K, d)
    assert N * K // 8 < held_row_bound(N * K, held, cfg.num_experts) < N * K  # the one-body form
    layer = MoEMLP(cfg)
    one_chip = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = place(jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, d), DT)))["params"])

    def loss(p, x):
        y, aux = layer.apply({"params": p}, x)
        return jnp.sum(y.astype(jnp.float32) ** 2) + aux[0]

    with jax.default_matmul_precision("highest"):
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(params, place(_s(tokens + (d,)))).compile().as_text()
    assert (len(re.findall(r" while\(", text)), len(re.findall(r" conditional\(", text))) == (2, 0)
    assert f"[{N},{K},{d}]" not in text
    by_row = re.compile(rf"^\s*(?:ROOT )?(%\S+) = (?:bf16|f32)\[{N * K},{d}\]\S* ([\w-]+)\(")
    moved, fused = [], False
    for line in text.splitlines():
        if line and not line.startswith(" "):  # a computation's header
            fused = "fused_computation" in line
        m = by_row.match(line)
        if m and not fused and (m.group(2) in ("reshape", "copy", "transpose") or re.search("copy|transpose|reshape", m.group(1))):
            moved.append(line.strip()[:120])
    assert moved == [], moved
    assert len(re.findall(rf"= bf16\[{N * K},{d}\]\S* custom-call\(", text)) == 2  # the two rooms


# ---------------------------------------------------------------------------
# the PPO learner's ladder of widths: one train-step program per rung
# ---------------------------------------------------------------------------

# what the compiler gives the parent's one train step of ``gptj6b_ppo_hh``
# (8 x (896 + 128), arguments + outputs + temporaries - aliased), PR 29's tree
PARENT_STEP_BYTES_AT_1024 = 9_329_875_968


@pytest.fixture(scope="module")
def gptj_ppo_step(topo):
    """The abstract PPO trainer of ``gptj-6b-l4`` as ``gptj6b_ppo_hh`` builds
    it (published widths, depth 4, two layers unfrozen, bf16), on one
    described v5e, with its train step and the state's shapes."""
    import dataclasses

    from chipbench import job
    from trlx_tpu import perf
    from trlx_tpu.parallel.mesh import make_mesh, set_global_mesh
    from trlx_tpu.parallel.sharding import param_shardings
    from trlx_tpu.trainer.base import _optimizer_state_shardings

    cell = job.find_cell("gptj6b_ppo_hh")
    cfg = job.build_config(job.load_config(cell["config"]), job.load_json("traffic", cell["traffic"]),
                           0, toy=False, ckpt_dir="/nonexistent")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        # the trainer makes its mesh of every device JAX has (eight virtual
        # CPUs under these tests) and places its step counter there: build
        # it on one of them, then hand it the one described chip
        import trlx_tpu.trainer.base as base

        mp.setattr(base, "make_mesh", lambda parallel: make_mesh(parallel, devices=jax.devices()[:1]))
        trainer = perf._build_abstract_trainer(cfg)
        mesh = trainer.mesh = make_mesh(cfg.parallel, devices=topo.devices[:1])
        place = lambda tree, sh: jax.tree_util.tree_map(  # noqa: E731
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, sh)
        params = place(trainer.state.params, param_shardings(trainer.state.params, mesh))
        opt = place(trainer.state.opt_state,
                    _optimizer_state_shardings(mesh, params, trainer.state.opt_state))
        everywhere = NamedSharding(mesh, P())
        state = dataclasses.replace(
            trainer.state, params=params, opt_state=opt,
            step=jax.ShapeDtypeStruct((), np.int32, sharding=everywhere),
            rng=jax.ShapeDtypeStruct(trainer.state.rng.shape, trainer.state.rng.dtype,
                                     sharding=everywhere))
        set_global_mesh(mesh)
        try:
            yield trainer, trainer._build_train_step(), state, mesh
        finally:
            set_global_mesh(None)


@pytest.mark.parametrize("query_width", [256, 512, 896])
def test_ppo_train_step_compiles_at_each_ladder_width(gptj_ppo_step, monkeypatch, query_width):
    """The learner's loader pads a minibatch of ``gptj6b_ppo_hh`` to one of
    three widths (``length_ladder(896)`` + 128 new tokens): each is a program
    of its own and must lower and compile for the chip, flash kernels in it,
    and the widest (the parent's only one) may need no more memory than the
    parent's. Bytes from the compiler; nothing runs."""
    from trlx_tpu import perf
    from trlx_tpu.pipeline.ppo_pipeline import length_ladder

    trainer, step, state, mesh = gptj_ppo_step
    assert length_ladder(896) == (256, 512, 896) and length_ladder(128) == (128,)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=NamedSharding(mesh, P()))
             for k, v in perf._train_batch_sds("ppotrainer", 8, query_width, 128).items()}
    with mesh:
        compiled = step.lower(state, batch, jax.ShapeDtypeStruct((), np.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total <= PARENT_STEP_BYTES_AT_1024, (query_width, total)
    if query_width == 896:
        assert total > 0.95 * PARENT_STEP_BYTES_AT_1024  # the same program, not a smaller one
