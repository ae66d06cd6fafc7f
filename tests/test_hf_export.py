"""HF-format export round-trip: torch → trlx_tpu → exported directory →
``transformers.from_pretrained`` → identical logits; heads merged under the
reference's ``v_head.`` / ``ilql_heads.`` prefixes
(``trlx/models/modeling_ppo.py:306-328``, ``modeling_ilql.py:322-344``,
``accelerate_base_trainer.py:256-272``).
"""

import numpy as np
import pytest

from trlx_tpu.data.configs import ModelConfig
from trlx_tpu.models import hf_interop
from trlx_tpu.models.builder import build_causal_lm

from tests.test_models import _tiny_hf


@pytest.mark.parametrize("family", ["gpt2", "llama", "gpt_neox", "gptj", "opt", "bloom", "mistral", "mixtral", "olmoe"])
def test_roundtrip_exact_logits(family, tmp_path):
    """import tiny torch model → export → reload in transformers → exact parity."""
    import torch
    import transformers

    hf, params, cfg = _tiny_hf(family)
    out_dir = str(tmp_path / family)
    hf_interop.save_pretrained_hf(out_dir, params, cfg)

    reloaded = transformers.AutoModelForCausalLM.from_pretrained(out_dir)
    reloaded.eval()
    ids = torch.tensor(np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 12)))
    with torch.no_grad():
        ref = hf(ids).logits.numpy()
        got = reloaded(ids).logits.numpy()
    np.testing.assert_allclose(got, ref, atol=1e-6)


@pytest.mark.parametrize("variant", ["t5", "flan"])
def test_t5_roundtrip_exact_logits(variant, tmp_path):
    """Seq2seq leg of the reference save path (VERDICT r2 #5,
    ``modeling_ppo.py:1036-1113,306-328``): torch T5 → trlx_tpu → exported
    directory → ``AutoModelForSeq2SeqLM.from_pretrained`` → exact parity.
    Covers both the tied-embedding relu (v1.0) and untied gated-gelu
    (v1.1/flan) variants."""
    import torch
    import transformers

    from tests.test_seq2seq import _tiny_hf as _tiny_t5

    hf, params, cfg = _tiny_t5(variant)
    out_dir = str(tmp_path / variant)
    hf_interop.save_pretrained_hf(out_dir, params, cfg)

    reloaded = transformers.AutoModelForSeq2SeqLM.from_pretrained(out_dir)
    reloaded.eval()
    rs = np.random.RandomState(0)
    ids = torch.tensor(rs.randint(1, cfg.vocab_size, (2, 10)))
    dec = torch.tensor(rs.randint(1, cfg.vocab_size, (2, 6)))
    with torch.no_grad():
        ref = hf(input_ids=ids, decoder_input_ids=dec).logits.numpy()
        got = reloaded(input_ids=ids, decoder_input_ids=dec).logits.numpy()
    np.testing.assert_allclose(got, ref, atol=1e-6)


def test_t5_head_prefix_merge(tmp_path):
    """A T5 PPO value head rides along under the reference's ``v_head.``
    prefix, so the exported checkpoint hands back to reference trlx's
    seq2seq wrapper too."""
    from trlx_tpu.models.builder import build_seq2seq_lm

    module, params, scfg = build_seq2seq_lm(
        ModelConfig("builtin:t5-test", model_arch_type="seq2seq"), head="value"
    )
    sd = hf_interop.params_to_hf_state_dict(params, scfg)
    assert "v_head.0.weight" in sd and "v_head.2.weight" in sd
    assert "shared.weight" in sd and "lm_head.weight" in sd
    # transformers must still load it (heads ignored)
    import transformers

    out_dir = str(tmp_path / "t5_vhead")
    hf_interop.save_pretrained_hf(out_dir, params, scfg)
    model = transformers.AutoModelForSeq2SeqLM.from_pretrained(out_dir)
    assert model.config.d_model == scfg.hidden_size


def test_head_prefix_merge(tmp_path):
    import torch

    module, params, tcfg = build_causal_lm(ModelConfig("builtin:gpt2-test"), head="value")
    sd = hf_interop.params_to_hf_state_dict(params, tcfg)
    assert "v_head.0.weight" in sd and "v_head.2.weight" in sd
    # torch Linear layout: [out, in]
    assert sd["v_head.0.weight"].shape == (2 * tcfg.hidden_size, tcfg.hidden_size)
    assert sd["v_head.2.weight"].shape == (1, 2 * tcfg.hidden_size)

    module, params, tcfg = build_causal_lm(ModelConfig("builtin:gpt2-test"), head="ilql")
    sd = hf_interop.params_to_hf_state_dict(params, tcfg)
    for key in (
        "ilql_heads.heads.v_head.0.weight",
        "ilql_heads.heads.q_heads.0.2.weight",
        "ilql_heads.heads.q_heads.1.0.bias",
        "ilql_heads.heads.target_q_heads.0.0.weight",
    ):
        assert key in sd, key

    out_dir = str(tmp_path / "ilql")
    hf_interop.save_pretrained_hf(out_dir, params, tcfg)
    bin_sd = torch.load(out_dir + "/pytorch_model.bin", weights_only=True)
    assert "ilql_heads.heads.q_heads.0.0.weight" in bin_sd


def test_scan_layout_exports_identically():
    from trlx_tpu.models.transformer import stack_layer_params

    _, params, cfg = _tiny_hf("gpt2")
    sd_flat = hf_interop.params_to_hf_state_dict(params, cfg)
    scan_cfg = cfg.__class__(**{**cfg.__dict__, "scan_layers": True})
    stacked = {"backbone": stack_layer_params(params["backbone"], cfg.num_layers)}
    sd_scan = hf_interop.params_to_hf_state_dict(stacked, scan_cfg)
    assert sd_flat.keys() == sd_scan.keys()
    for k in sd_flat:
        np.testing.assert_array_equal(np.asarray(sd_flat[k]), np.asarray(sd_scan[k]), err_msg=k)


def test_lora_merged_on_export():
    """Trained adapters fold into kernels at export (W += (alpha/r)·AB)."""
    module, params, tcfg = build_causal_lm(
        ModelConfig(
            "builtin:gpt2-test",
            peft_kwargs={"peft_type": "lora", "r": 4, "lora_alpha": 8, "modified_modules": "attention"},
        ),
        head="value",
    )
    # make the adapter non-trivial so the merge is observable
    import jax.numpy as jnp

    a = params["backbone"]["h_0"]["attn"]["q_proj"]["lora_a"]
    b = jnp.ones_like(params["backbone"]["h_0"]["attn"]["q_proj"]["lora_b"]) * 0.01
    params["backbone"]["h_0"]["attn"]["q_proj"]["lora_b"] = b
    sd = hf_interop.params_to_hf_state_dict(params, tcfg)
    base = np.asarray(params["backbone"]["h_0"]["attn"]["q_proj"]["kernel"])
    merged = np.asarray(sd["transformer.h.0.attn.c_attn.weight"])[:, : tcfg.hidden_size]
    expected = base + (np.asarray(a) @ np.asarray(b)) * (tcfg.lora_alpha / tcfg.lora_r)
    np.testing.assert_allclose(merged, expected, atol=1e-6)
    assert not any("lora" in k for k in sd)


def test_trainer_save_pretrained_writes_hf(tmp_path):
    """TPUBaseTrainer.save_pretrained emits a transformers-loadable dir."""
    import transformers

    from trlx_tpu.data.default_configs import default_sft_config
    from trlx_tpu.trainer import get_trainer
    import trlx_tpu.trainer.sft  # noqa: F401

    cfg = default_sft_config().evolve(
        train=dict(
            seq_length=32,
            batch_size=8,
            total_steps=1,
            eval_interval=100,
            checkpoint_interval=100,
            epochs=1,
            checkpoint_dir=str(tmp_path / "ckpts"),
            tracker=None,
        ),
        model=dict(model_path="builtin:gpt2-test"),
    )
    trainer = get_trainer(cfg.train.trainer)(
        config=cfg, reward_fn=None, metric_fn=None, stop_sequences=[]
    )
    out = str(tmp_path / "hf_out")
    trainer.save_pretrained(out)
    model = transformers.AutoModelForCausalLM.from_pretrained(out)
    assert model.config.vocab_size == trainer.tcfg.vocab_size


def test_t5_lora_merged_on_export():
    """A LoRA-tuned T5 exports with adapters folded into the kernels
    (same exact-merge semantics as the causal families)."""
    import jax.numpy as jnp

    from trlx_tpu.models.builder import build_seq2seq_lm

    module, params, scfg = build_seq2seq_lm(
        ModelConfig(
            "builtin:t5-test", model_arch_type="seq2seq",
            peft_kwargs={"peft_type": "lora", "r": 4, "lora_alpha": 8,
                         "modified_modules": "attention"},
        ),
        head="value",
    )
    proj = params["backbone"]["dec_0"]["cross_attn"]["q_proj"]
    proj["lora_b"] = jnp.ones_like(proj["lora_b"]) * 0.01
    sd = hf_interop.params_to_hf_state_dict(params, scfg)
    base = np.asarray(proj["kernel"])
    expected = base + (np.asarray(proj["lora_a"]) @ np.asarray(proj["lora_b"])) * (
        scfg.lora_alpha / scfg.lora_r
    )
    merged = np.asarray(sd["decoder.block.0.layer.1.EncDecAttention.q.weight"]).T
    np.testing.assert_allclose(merged, expected, atol=1e-6)
    assert not any("lora" in k for k in sd)


def test_push_to_hub_payload(tmp_path):
    """``push_to_hub`` stages a complete ``save_pretrained`` export and hands
    the staged directory to the upload step in one call (reference
    capability: ``modeling_base.py:30`` inherits ``PushToHubMixin``).
    Offline-safe: with ``uploader=`` injected, no network is touched."""
    import json
    import os

    from trlx_tpu.utils.checkpoint import push_to_hub

    _, params, cfg = build_causal_lm(
        ModelConfig(model_path="builtin:gpt2-test"), head="value"
    )
    seen = {}

    def uploader(repo_id, staged):
        seen["repo_id"] = repo_id
        seen["files"] = sorted(os.listdir(staged))
        with open(os.path.join(staged, "trlx_tpu_config.json")) as f:
            seen["config"] = json.load(f)
        return f"local://{repo_id}"

    url = push_to_hub(
        "org/tiny-gpt2-rlhf",
        params,
        cfg,
        tokenizer_path="builtin:bytes",
        uploader=uploader,
    )
    assert url == "local://org/tiny-gpt2-rlhf"
    assert seen["repo_id"] == "org/tiny-gpt2-rlhf"
    # native export + HF torch export both present, so the published repo is
    # loadable by plain transformers (value head under the v_head. prefix)
    for name in ("flax_model.msgpack", "trlx_tpu_config.json", "pytorch_model.bin", "config.json"):
        assert name in seen["files"], seen["files"]
    assert seen["config"]["tokenizer_path"] == "builtin:bytes"


def test_push_to_hub_staging_dir_persists(tmp_path):
    """An explicit staging_dir keeps the export on disk after upload — the
    manual-recovery path the error message points at."""
    from trlx_tpu.utils.checkpoint import push_to_hub

    _, params, cfg = build_causal_lm(ModelConfig(model_path="builtin:gpt2-test"))
    staged_dir = str(tmp_path / "staged")
    push_to_hub(
        "org/x", params, cfg, staging_dir=staged_dir, uploader=lambda r, d: r
    )
    assert (tmp_path / "staged" / "flax_model.msgpack").exists()


def test_push_to_hub_failure_keeps_staged_export(tmp_path):
    """If the upload step fails after staging, the export survives for
    manual recovery (the error log points at it) instead of vanishing with
    the temp dir."""
    import glob

    from trlx_tpu.utils.checkpoint import push_to_hub

    _, params, cfg = build_causal_lm(ModelConfig(model_path="builtin:gpt2-test"))

    def boom(repo_id, staged):
        raise ConnectionError("hub unreachable")

    before = set(glob.glob("/tmp/trlx_tpu_hub_*"))
    with pytest.raises(ConnectionError):
        push_to_hub("org/x", params, cfg, uploader=boom)
    kept = set(glob.glob("/tmp/trlx_tpu_hub_*")) - before
    assert len(kept) == 1
    import os
    import shutil

    staged = kept.pop()
    assert os.path.exists(os.path.join(staged, "flax_model.msgpack"))
    shutil.rmtree(staged)
