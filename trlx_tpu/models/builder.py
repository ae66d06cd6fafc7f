"""Model assembly: ModelConfig → (flax module, params).

The reference's ``get_arch`` + ``PreTrainedModelWrapper.from_pretrained``
(``trlx/trainer/accelerate_ppo_trainer.py:120-134``,
``trlx/models/modeling_base.py:53-141``) equivalent: resolves a model spec
(``builtin:<family>-<size>`` or a local HF checkpoint path), builds the
appropriate wrapper module (plain / value-head / ILQL-heads), initializes or
imports weights, and reports which params the hydra reference branch needs.
"""

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.data.configs import ModelConfig, ParallelConfig
from trlx_tpu.models.heads import CausalLMWithILQLHeads, CausalLMWithValueHead
from trlx_tpu.models.transformer import (
    CausalTransformer,
    TransformerConfig,
    config_from_spec,
)
from trlx_tpu.parallel.sharding import param_shardings, shard_params

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}


LORA_TARGET_GROUPS = {
    "attention": ("q_proj", "k_proj", "v_proj", "o_proj"),
    "mlp": ("gate_proj", "up_proj", "down_proj"),
}
LORA_TARGET_GROUPS["all"] = LORA_TARGET_GROUPS["attention"] + LORA_TARGET_GROUPS["mlp"]


def parse_peft_overrides(peft_kwargs: Dict[str, Any]) -> Dict[str, Any]:
    """ModelConfig.peft_kwargs → backbone config overrides (reference
    ``parse_delta_kwargs``, ``trlx/utils/modeling.py:419-450``; like the
    reference, only LoRA is supported)."""
    kw = dict(peft_kwargs)
    peft_type = str(kw.pop("peft_type", kw.pop("delta_type", "lora"))).lower()
    if peft_type != "lora":
        raise ValueError(f"Only LoRA peft is supported (got '{peft_type}')")
    modified = kw.pop("modified_modules", "all")
    if isinstance(modified, str):
        if modified not in LORA_TARGET_GROUPS:
            raise ValueError(
                f"modified_modules '{modified}' not in {sorted(LORA_TARGET_GROUPS)}; "
                "pass an explicit list of projection names instead"
            )
        targets = LORA_TARGET_GROUPS[modified]
    else:
        targets = tuple(modified)
    out = dict(
        lora_r=int(kw.pop("r", kw.pop("lora_r", 8))),
        lora_alpha=float(kw.pop("lora_alpha", 16.0)),
        lora_targets=targets,
    )
    if kw:
        raise ValueError(f"Unknown peft_kwargs keys: {sorted(kw)}")
    return out


def merge_trees(base: Dict[str, Any], override: Dict[str, Any]) -> Dict[str, Any]:
    """Deep-merge ``override`` into ``base`` (override wins on leaves). Used to
    overlay imported HF weights onto an initialized tree without dropping
    params the checkpoint does not carry (LoRA adapters, fresh heads)."""
    out = dict(base)
    for k, v in override.items():
        if k in out and isinstance(out[k], dict) and isinstance(v, dict):
            out[k] = merge_trees(out[k], v)
        else:
            out[k] = v
    return out


def merge_lora_params(params: Dict[str, Any], cfg) -> Dict[str, Any]:
    """Fold trained adapters into their kernels (``W += (alpha/r)·AB``) and
    drop the lora leaves — for HF-format export of a LoRA-tuned model."""
    if not getattr(cfg, "lora_r", 0):
        return params  # nothing to fold
    scale = cfg.lora_alpha / cfg.lora_r

    def fold(tree):
        if not isinstance(tree, dict):
            return tree
        if "lora_a" in tree and "kernel" in tree:
            out = {k: v for k, v in tree.items() if k not in ("lora_a", "lora_b")}
            out["kernel"] = tree["kernel"] + (
                np.asarray(tree["lora_a"]) @ np.asarray(tree["lora_b"])
            ) * scale
            return out
        return {k: fold(v) for k, v in tree.items()}

    return fold(params)



def _assemble_overrides(
    model_config: ModelConfig,
    parallel: Optional[ParallelConfig],
    scan_layers_supported: bool = True,
) -> Dict[str, Any]:
    """Shared config-override assembly for both architectures: user extras,
    peft translation, and parallel-derived dtypes/remat."""
    overrides: Dict[str, Any] = dict(model_config.model_extra_kwargs or {})
    if not scan_layers_supported:
        overrides.pop("scan_layers", None)
    if model_config.peft_kwargs:
        overrides.update(parse_peft_overrides(model_config.peft_kwargs))
    if parallel is not None:
        overrides.setdefault("param_dtype", DTYPES[parallel.param_dtype])
        overrides.setdefault("dtype", DTYPES[parallel.compute_dtype])
        overrides.setdefault("remat", parallel.remat)
        if scan_layers_supported:
            overrides.setdefault("scan_layers", parallel.scan_layers or parallel.pipe > 1)
            overrides.setdefault("pipe_microbatches", parallel.pipe_microbatches)
    return overrides


def _import_hf_backbone(params, head, backbone_numpy, param_dtype):
    """Overlay imported HF weights onto initialized params (deep merge keeps
    LoRA adapters and fresh heads)."""
    backbone = jax.tree_util.tree_map(
        lambda x: jnp.asarray(x, param_dtype), backbone_numpy
    )
    if head is None:
        return merge_trees(params, backbone)
    params = dict(params)
    params["backbone"] = merge_trees(params["backbone"], backbone)
    return params


def _build_params(make_params, seed: int, mesh, abstract: bool, load_backbone=None, stored=None):
    """``make_params(seed)`` (initializers, target-Q sync; the dummy forward
    that ``module.init`` traces is dead code, pruned before lowering) as ONE
    jitted program whose outputs are born under ``param_shardings`` when a
    ``mesh`` is given, instead of an eager walk that compiles a program an
    operation at every start. The seed is an argument, so one compiled
    program serves every seed (a constant would be a new program, and a new
    compile, a seed). ``abstract`` stops at the shapes (the trace they cost
    is the one ``jit`` then finds in its cache). ``load_backbone(params)``
    overlays pretrained host arrays, which are then placed leaf by leaf.
    ``stored(name, fn, jit_kwargs)`` makes the program one of a job's stored
    ones (``utils/programs.py``): the shapes, which ``out_shardings`` needs, are
    then traced on a miss only."""
    # an int64 scalar becomes the int32 that ``PRNGKey(<Python int>)`` makes
    seed = np.int64(seed)
    if abstract:
        return jax.eval_shape(make_params, seed)

    def jit_kwargs():
        if mesh is None:
            return {}
        return {"out_shardings": param_shardings(jax.eval_shape(make_params, seed), mesh)}

    if stored is None:
        params = jax.jit(make_params, **jit_kwargs())(seed)
    else:
        params = stored("make_params", make_params, jit_kwargs)(seed)
    if load_backbone is not None:
        params = load_backbone(params)
        if mesh is not None:
            params = shard_params(params, mesh)
    return params


def _stored_by(programs, module, cfg, head, two_qs):
    """``_build_params``'s ``stored`` for a job's ``ProgramStore``: the program
    is kept by the module's classes and the model's resolved config."""
    if programs is None:
        return None
    programs.extend(classes=type(module).__mro__)
    return lambda name, fn, jit_kwargs: programs.program(
        name, fn, repr(cfg), head, two_qs, jit_kwargs=jit_kwargs, once=True)


def resolve_transformer_config(
    model_config: ModelConfig, parallel: Optional[ParallelConfig] = None
) -> Tuple[TransformerConfig, Optional[str]]:
    """Resolve (TransformerConfig, hf_path or None) from a ModelConfig."""
    import dataclasses

    path = model_config.model_path
    overrides = _assemble_overrides(model_config, parallel)

    if path.startswith("builtin:"):
        return config_from_spec(path, **overrides), None

    from trlx_tpu.models.hf_interop import config_from_hf
    from transformers import AutoConfig

    cfg = config_from_hf(AutoConfig.from_pretrained(path))
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg, path


def build_causal_lm(
    model_config: ModelConfig,
    parallel: Optional[ParallelConfig] = None,
    head: Optional[str] = None,  # None | "value" | "ilql"
    two_qs: bool = True,
    seed: int = 0,
    abstract: bool = False,
    mesh: Optional[Any] = None,
    programs: Optional[Any] = None,
) -> Tuple[Any, Dict[str, Any], TransformerConfig]:
    """Build module + params. Pretrained weights (HF torch) replace the
    backbone subtree; heads stay freshly initialized. With a ``mesh`` the
    params come back placed per ``parallel.sharding.param_shardings``.

    ``abstract=True`` returns a ``ShapeDtypeStruct`` pytree instead of real
    arrays (and skips any pretrained-weight load): enough to lower/compile
    the training programs for cost/memory analysis without materializing a
    multi-GB model (``trlx_tpu/perf.py``). ``programs`` is the job's
    ``ProgramStore``: ``make_params`` is then kept by the model's config."""
    tcfg, hf_path = resolve_transformer_config(model_config, parallel)

    if head == "value":
        module = CausalLMWithValueHead(tcfg)
    elif head == "ilql":
        module = CausalLMWithILQLHeads(tcfg, two_qs=two_qs)
    else:
        module = CausalTransformer(tcfg)

    def make_params(seed):
        p = module.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
        if head == "ilql":
            # target-Q heads start as exact copies of the Q heads (reference
            # deepcopies them at init, modeling_ilql.py:154) — training toward
            # fresh random targets would be noise until many Polyak syncs.
            from trlx_tpu.models.heads import sync_target_q_params

            p = sync_target_q_params(p, alpha=1.0)
        return p

    def load_backbone(params):
        from trlx_tpu.models.hf_interop import load_pretrained

        hf_params, _ = load_pretrained(hf_path)
        backbone = hf_params["backbone"]
        if tcfg.scan_layers:
            from trlx_tpu.models.transformer import stack_layer_params

            backbone = stack_layer_params(backbone, tcfg.num_layers)
        return _import_hf_backbone(params, head, backbone, tcfg.param_dtype)

    params = _build_params(
        make_params, seed, mesh, abstract, load_backbone if hf_path is not None else None,
        _stored_by(programs, module, tcfg, head, two_qs),
    )
    return module, params, tcfg


def hydra_ref_params(params: Dict[str, Any], tcfg: TransformerConfig, num_layers_unfrozen: int) -> Dict[str, Any]:
    """Extract the frozen reference branch: top ``num_layers_unfrozen`` blocks
    + final norm + lm head (+ tied embedding). A small pytree snapshot taken
    at setup — the GSPMD analogue of the reference's deepcopy'd hydra heads
    (``modeling_ppo.py:331-391``)."""
    backbone = params["backbone"] if "backbone" in params else params
    keep = {}
    start = tcfg.num_layers - num_layers_unfrozen
    if tcfg.scan_layers:
        keep["h_scan"] = {
            "block": jax.tree_util.tree_map(
                lambda p: p[start:], backbone["h_scan"]["block"]
            )
        }
    else:
        for i in range(start, tcfg.num_layers):
            keep[f"h_{i}"] = backbone[f"h_{i}"]
    if tcfg.final_norm:
        keep["ln_f"] = backbone["ln_f"]
    if tcfg.tie_word_embeddings:
        keep["wte"] = backbone["wte"]
    else:
        keep["lm_head"] = backbone["lm_head"]
    return jax.tree_util.tree_map(lambda x: x, keep)  # shallow copy




def _mark(tree, value: bool):
    return jax.tree_util.tree_map(lambda _: value, tree)


def _mark_lora(tree, layer_in_range: bool):
    """True only on adapter leaves (``lora_*``) when the layer is in the
    unfrozen range — the base always freezes under LoRA."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: layer_in_range
        and str(getattr(path[-1], "key", "")).startswith("lora_"),
        tree,
    )


def _mask_heads(subtree):
    return {
        name: _mark(tree, not name.startswith("target_q_head"))
        for name, tree in subtree.items()
    }


def _scan_layer_vector(tcfg, num_layers_unfrozen: int):
    """Per-layer 0/1 trainability over the stacked layer dim, or None when
    every layer trains (``num_layers_unfrozen == -1``)."""
    if num_layers_unfrozen < 0:
        return None
    vec = np.zeros(tcfg.num_layers, np.float32)
    if num_layers_unfrozen > 0:
        vec[tcfg.num_layers - num_layers_unfrozen :] = 1.0
    return vec


def _mask_scan_blocks(layer_tree, tcfg, num_layers_unfrozen: int, lora: bool):
    """Mask leaves for the stacked ``h_scan`` subtree: bools where uniform,
    a per-layer 0/1 vector where only some layers train (consumed by
    ``get_optimizer``'s layer-wise freeze)."""
    vec = _scan_layer_vector(tcfg, num_layers_unfrozen)
    if lora:

        def leaf_mask(path, _):
            if not str(getattr(path[-1], "key", "")).startswith("lora_"):
                return False
            return True if vec is None else vec

        return jax.tree_util.tree_map_with_path(leaf_mask, layer_tree)
    if vec is None or vec.all():
        return _mark(layer_tree, True)
    if not vec.any():
        return _mark(layer_tree, False)
    return jax.tree_util.tree_map(lambda _: vec, layer_tree)


def trainable_mask(
    params: Dict[str, Any], tcfg: TransformerConfig, num_layers_unfrozen: int
) -> Dict[str, Any]:
    """Bool pytree: True for trainable leaves. ``num_layers_unfrozen == -1``
    trains everything; otherwise the top-k blocks ``h_<i>`` train and the
    blocks below them freeze, while EVERY other name of the backbone trains:
    the token (and position) embedding ``wte`` / ``wpe`` in front of the
    blocks, the final norm, the lm head; and so does every top-level tree
    beside the backbone (value head, Q and V heads) except ILQL's target-Q
    heads, which never train (reference ``freeze_bottom_causal_layers``,
    ``trlx/utils/modeling.py:34-44``), and a next-token-prediction module
    ``mtp_<k>``, which no loss reads. A policy without a ``backbone`` key (GRPO's
    bare transformer) has nothing this function freezes: every leaf trains,
    whatever ``num_layers_unfrozen`` says. Under ``scan_layers`` the stacked
    ``h_scan`` leaves get a per-layer 0/1 vector where only some layers train.

    With LoRA enabled (``tcfg.lora_r > 0``) the base model freezes entirely
    and only adapter leaves in the unfrozen-layer range plus heads train
    (reference: OpenDelta freezes the base and trains layer-ranged
    modified_modules, ``trlx/utils/modeling.py:389-417``).

    The train step takes no gradient with respect to a leaf marked ``False``
    (:func:`is_frozen`, ``trainer/base.py::_build_train_step``)."""

    lora = getattr(tcfg, "lora_r", 0) > 0
    mask: Dict[str, Any] = {}
    for top_key, subtree in params.items():
        if top_key == "backbone":
            sub = {}
            for name, layer_tree in subtree.items():
                if name == "h_scan":
                    sub[name] = _mask_scan_blocks(
                        layer_tree, tcfg, num_layers_unfrozen, lora
                    )
                    continue
                if name.startswith("mtp_"):
                    # a next-token-prediction module: the rollout sampler's drafter,
                    # which no loss reads (TransformerConfig.mtp_layers)
                    sub[name] = _mark(layer_tree, False)
                    continue
                if name.startswith("h_"):
                    in_range = (
                        num_layers_unfrozen < 0
                        or int(name[2:]) >= tcfg.num_layers - num_layers_unfrozen
                    )
                else:
                    in_range = True
                if lora:
                    sub[name] = _mark_lora(layer_tree, in_range and name.startswith("h_"))
                else:
                    sub[name] = _mark(layer_tree, in_range)
            mask[top_key] = sub
        elif top_key == "ilql_heads":
            mask[top_key] = _mask_heads(subtree)
        else:
            mask[top_key] = _mark(subtree, True)
    return mask


def is_frozen(leaf) -> bool:
    """Whether a mask leaf freezes its whole parameter: the bool ``False``.
    A per-layer 0/1 vector freezes layers of a stacked leaf, which the train
    step still differentiates whole (``get_optimizer`` masks its update)."""
    return isinstance(leaf, (bool, np.bool_)) and not leaf


def grad_param_frac(params, mask) -> float:
    """Parameters the train step differentiates over parameters in ``params``
    (``learn/grad_param_frac``): 1.0 where ``mask`` is None or freezes nothing."""
    if mask is None:
        return 1.0
    sizes = [int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params)]
    frozen = [is_frozen(m) for m in jax.tree_util.tree_leaves(mask)]
    return sum(n for n, f in zip(sizes, frozen) if not f) / max(sum(sizes), 1)


# ---------------------------------------------------------------------------
# seq2seq (T5) assembly — reference seq2seq arch selection
# (``trlx/trainer/accelerate_ppo_trainer.py:120-134`` picks the Seq2Seq
# wrappers when ``config.model.model_arch_type == "seq2seq"``).
# ---------------------------------------------------------------------------


def resolve_seq2seq_config(
    model_config: ModelConfig, parallel: Optional[ParallelConfig] = None
):
    """Resolve (Seq2SeqConfig, hf_path or None) from a ModelConfig."""
    import dataclasses

    from trlx_tpu.models.seq2seq import Seq2SeqConfig

    if parallel is not None and parallel.pipe > 1:
        raise ValueError(
            "pipeline parallelism (parallel.pipe > 1) is not supported for "
            "seq2seq models — the pipe schedule runs over the causal "
            "scan_layers block stack; use fsdp/model/data axes for T5"
        )
    path = model_config.model_path
    overrides = _assemble_overrides(model_config, parallel, scan_layers_supported=False)

    if path.startswith("builtin:"):
        spec = path.split(":", 1)[1]
        family, _, size = spec.partition("-")
        makers = {"t5": Seq2SeqConfig.t5, "flan_t5": Seq2SeqConfig.flan_t5}
        if family not in makers:
            raise ValueError(f"Unknown seq2seq family '{family}'. Known: {sorted(makers)}")
        return makers[family](size or "test", **overrides), None

    from transformers import AutoConfig

    from trlx_tpu.models.hf_interop import seq2seq_config_from_hf

    cfg = seq2seq_config_from_hf(AutoConfig.from_pretrained(path))
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg, path


def build_seq2seq_lm(
    model_config: ModelConfig,
    parallel: Optional[ParallelConfig] = None,
    head: Optional[str] = None,  # None | "value" | "ilql"
    two_qs: bool = True,
    seed: int = 0,
    abstract: bool = False,
    mesh: Optional[Any] = None,
    programs: Optional[Any] = None,
):
    """Build seq2seq module + params (pretrained backbone import, fresh heads).

    ``abstract=True`` mirrors :func:`build_causal_lm`: a ShapeDtypeStruct
    pytree for lowering/compiling programs without materializing weights."""
    from trlx_tpu.models.heads import Seq2SeqLMWithILQLHeads, Seq2SeqLMWithValueHead
    from trlx_tpu.models.seq2seq import T5Transformer

    scfg, hf_path = resolve_seq2seq_config(model_config, parallel)

    if head == "value":
        module = Seq2SeqLMWithValueHead(scfg)
    elif head == "ilql":
        module = Seq2SeqLMWithILQLHeads(scfg, two_qs=two_qs)
    else:
        module = T5Transformer(scfg)

    def make_params(seed):
        p = module.init(
            jax.random.PRNGKey(seed),
            jnp.zeros((1, 8), jnp.int32),
            decoder_input_ids=jnp.zeros((1, 4), jnp.int32),
        )["params"]
        if head == "ilql":
            from trlx_tpu.models.heads import sync_target_q_params

            p = sync_target_q_params(p, alpha=1.0)
        return p

    def load_backbone(params):
        from trlx_tpu.models.hf_interop import load_pretrained_seq2seq

        hf_params, _ = load_pretrained_seq2seq(hf_path)
        return _import_hf_backbone(params, head, hf_params["backbone"], scfg.param_dtype)

    params = _build_params(
        make_params, seed, mesh, abstract, load_backbone if hf_path is not None else None,
        _stored_by(programs, module, scfg, head, two_qs),
    )
    return module, params, scfg


def seq2seq_hydra_ref_params(
    params: Dict[str, Any], scfg, num_layers_unfrozen: int
) -> Dict[str, Any]:
    """Frozen seq2seq reference branch: top ``num_layers_unfrozen`` *decoder*
    blocks + decoder final norm + rel-pos bias table + lm head/tied embedding
    (reference ``T5Branch``, ``modeling_ppo.py:1113-1222``)."""
    backbone = params["backbone"] if "backbone" in params else params
    keep = {}
    start = scfg.num_decoder_layers - num_layers_unfrozen
    for i in range(start, scfg.num_decoder_layers):
        keep[f"dec_{i}"] = backbone[f"dec_{i}"]
    keep["dec_ln_f"] = backbone["dec_ln_f"]
    keep["dec_rel_bias"] = backbone["dec_rel_bias"]
    if scfg.tie_word_embeddings:
        keep["wte"] = backbone["wte"]
    else:
        keep["lm_head"] = backbone["lm_head"]
    return jax.tree_util.tree_map(lambda x: x, keep)


def seq2seq_trainable_mask(
    params: Dict[str, Any], scfg, num_layers_unfrozen: int
) -> Dict[str, Any]:
    """Bool pytree for seq2seq freezing. Mirrors the reference's
    ``freeze_bottom_seq2seq_layers`` (``trlx/utils/modeling.py:47-66``):
    with k>0 unfrozen, the shared embedding, the whole encoder, both final
    norms, and all but the top-k decoder blocks freeze; the lm head and any
    value/Q heads stay trainable. At k=0 the reference freezes everything
    *except* the decoder blocks (``decoder.block[:-0] == []``), so the whole
    decoder trains — mirrored here for behavioral parity."""

    frozen_names = {"wte", "enc_ln_f", "dec_ln_f", "enc_rel_bias", "dec_rel_bias"}
    lora = getattr(scfg, "lora_r", 0) > 0
    mask: Dict[str, Any] = {}
    for top_key, subtree in params.items():
        if top_key == "backbone":
            sub = {}
            for name, layer_tree in subtree.items():
                is_dec_block = name.startswith("dec_") and name[4:].isdigit()
                if num_layers_unfrozen < 0:
                    trainable = True
                elif name.startswith("enc_") or name in frozen_names:
                    trainable = False
                elif is_dec_block:
                    trainable = (
                        num_layers_unfrozen == 0  # reference: k=0 trains all decoder blocks
                        or int(name[4:]) >= scfg.num_decoder_layers - num_layers_unfrozen
                    )
                else:
                    trainable = True  # lm_head
                if lora:
                    # adapters only, within the unfrozen decoder range
                    # (reference hardcodes the decoder prefix for T5,
                    # trlx/utils/modeling.py:400-402)
                    sub[name] = _mark_lora(layer_tree, trainable and is_dec_block)
                else:
                    sub[name] = _mark(layer_tree, trainable)
            mask[top_key] = sub
        elif top_key == "ilql_heads":
            mask[top_key] = _mask_heads(subtree)
        else:
            mask[top_key] = _mark(subtree, True)
    return mask
