"""A recurrent layer's cached state against the reference's recurrence, on the chip.

    python3 -m chipbench.state_check --workload minicpmsala_ppo_ctx16k --seed <n> [--fault bf16_state]

``run.py --checks-only`` (set-up, the warm-up cycle, checks 1 to 3 on the
collection that follows) with one more reading taken where check 1 takes its
own, on the same rows of the same rollouts: the sampler's two calls into the
model (``ops/sampling.py::generate``: a prefill of the prompt's slots from
slot 0 into ``make_kv_cache``'s cache, then one token a step at the next
slot) are made again, a row at a time, on the prompt and the first
``checks.RESP`` sampled tokens. Every ``state`` leaf the cache then holds is
compared with the reference's ``layer_states`` (float32, ``highest``, token
by token) OF THE INPUTS THAT LAYER SAW in the system (``ln_attn``'s output,
kept by ``capture_intermediates``), so that the reading is of the mixer and
its recurrence alone: against the reference's own stream the bfloat16 of
everything in front of a layer reads 1.0 to 1.9% on the state, and a state
held in bfloat16 only 1.3 times that (PERF.md section 6, PR 49).

- ``state_rel_l2_prefill``: after the prefill (the chunked scan's last carry),
- ``state_rel_l2_decode``: after the steps (the one-token update's),

each the relative L2 distance over one row's ``[heads, d, d]`` of one layer,
the worst row of the worst layer. ``--fault bf16_state`` rounds the
reference's state to bfloat16 after every token: the control of the state's
stated float32, which checks 1 and 2 cannot fail (the logits of a cut whose
mixers end in a norm, a gate and ``scale_depth / sqrt(32)`` move less under
it than from seed to seed: ``tolerances/minicpm-sala-9b-l8.json``).

The limits are the configuration's ``state_check`` in
``chipbench/tolerances/<config>.json``; the line ``{"state_check": ...}``
carries ``state_correct``. It decides nothing in ``run.py``: ``correct`` is
checks 1 to 4 alone, and ``checks.py`` takes no family's own deciding check
(PERF.md, Open questions, has the edit that would). A reference without
``layer_states`` has none to compare and the run is ``--checks-only`` as it was.
"""

import importlib
import json
import os
import re
import sys

import numpy as np

from chipbench import checks, run


def load_limits(config_name: str):
    with open(os.path.join(checks.HERE, "tolerances", f"{config_name}.json")) as f:
        return {k: float(v) for k, v in json.load(f).get("state_check", {}).get("limits", {}).items()}


def _layer_inputs(intermediates):
    """``{layer: ln_attn's output}`` out of a ``capture_intermediates`` tree, whatever wraps the blocks."""
    found = {}

    def walk(tree):
        for name, sub in tree.items():
            block = re.fullmatch(r"h_(\d+)", name)
            if block and "ln_attn" in sub:
                found[int(block.group(1))] = sub["ln_attn"]["__call__"][0]
            elif isinstance(sub, dict):
                walk(sub)

    walk(intermediates)
    return found


def state_readings(trainer, config_file, gen_out, fault=None):
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.transformer import make_kv_cache

    ref = importlib.import_module(f"chipbench.reference.{config_file['family']}")
    if not hasattr(ref, "layer_states"):
        return {}
    rows = checks.sample_rows(int(gen_out.sequences.shape[0]))
    P = int(gen_out.prompt_mask.shape[1])
    R = min(checks.RESP, int(gen_out.response_tokens.shape[1]))
    T = P + R
    seqs = np.asarray(jax.device_get(gen_out.sequences))[rows, :T]
    p_mask = np.asarray(jax.device_get(gen_out.prompt_mask))[rows].astype(np.int32)
    mask = np.concatenate([p_mask, np.asarray(jax.device_get(gen_out.response_mask))[rows, :R]], axis=1).astype(np.int32)
    params, module, tcfg = trainer.state.params, trainer.module, trainer.tcfg
    keep = dict(capture_intermediates=lambda mdl, method: mdl.name == "ln_attn", mutable=["intermediates"])

    @jax.jit
    def sampler_states(p, ids, m):
        """``generate``'s calls, with the sampled tokens fed back in."""
        slots = jnp.concatenate([m[:, :P], jnp.zeros((ids.shape[0], R), jnp.int32)], axis=1)
        out, seen = module.apply({"params": p}, ids[:, :P], attention_mask=slots, positions=None,
                                 cache=make_kv_cache(tcfg, ids.shape[0], T), cache_index=jnp.asarray(0, jnp.int32),
                                 logits_span=(P - 1, P), **keep)
        cache, seen = out["cache"], _layer_inputs(seen["intermediates"])
        prompt_len = jnp.sum(m[:, :P], axis=1).astype(jnp.int32)
        leaves = lambda c: {i: layer["state"] for i, layer in enumerate(c) if "state" in layer}

        def step(carry, j):
            cache, slots = carry
            slot = P + j
            slots = jax.lax.dynamic_update_slice_in_dim(slots, jax.lax.dynamic_slice_in_dim(m, slot, 1, 1), slot, 1)
            out, seen = module.apply({"params": p}, jax.lax.dynamic_slice_in_dim(ids, slot, 1, 1), attention_mask=slots,
                                     positions=(prompt_len + j)[:, None], cache=cache, cache_index=slot, **keep)
            return (out["cache"], slots), {i: u[:, 0] for i, u in _layer_inputs(seen["intermediates"]).items()}

        (after, _), stepped = jax.lax.scan(step, (cache, slots), jnp.arange(R, dtype=jnp.int32))
        held = leaves(cache)
        inputs = {i: jnp.concatenate([seen[i], jnp.moveaxis(stepped[i], 0, 1)], axis=1) for i in held}
        return held, leaves(after), inputs

    def rel(a, b):  # [H, d, d] of one row of one layer
        # the cache holds a head's state values by keys (``ops/ssd.py``: [B, H, P, N]), the reference keys by values
        a, b = jnp.asarray(a, jnp.float32), jnp.swapaxes(b, -1, -2)
        return float(jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b**2)))

    by_layer = {}  # layer -> [rows, (prefill, decode)]
    for r in range(rows.size):  # a row at a time: the kept inputs of 16384 slots are 126 MB a layer a row
        ids, m = jnp.asarray(seqs[r : r + 1]), jnp.asarray(mask[r : r + 1])
        held, after, inputs = sampler_states(params, ids, m)
        want = ref.layer_states(checks.backbone_of(params), config_file["published"], inputs, m, (P - 1, T - 1), fault=fault)
        for i, S in want.items():
            by_layer.setdefault(i, []).append((rel(held[i][0], S[0, 0]), rel(after[i][0], S[1, 0])))
    if not by_layer:
        return {}
    out = {"state_layers": sorted(by_layer), "state_rows": int(rows.size), "state_steps": R}
    for k, name in enumerate(("prefill", "decode")):
        worst = {i: max(row[k] for row in got) for i, got in by_layer.items()}
        out[f"state_rel_l2_{name}"] = max(worst.values())
        out[f"state_rel_l2_{name}_layers"] = [worst[i] for i in sorted(worst)]
    return out


def verdict(values, limits) -> bool:
    return bool(limits) and all(np.isfinite(values.get(k, np.nan)) and values[k] <= v for k, v in limits.items())


def main(argv=None) -> int:
    model_checks = checks.model_checks

    def with_states(trainer, config_file, gen_out, fault=None):
        values = state_readings(trainer, config_file, gen_out, fault=fault)
        if values:
            limits = load_limits(config_file["name"])
            run.say(state_check=values, limits=limits, state_correct=verdict(values, limits))
        return model_checks(trainer, config_file, gen_out, fault=fault)

    checks.model_checks = with_states
    try:
        return run.main(list(sys.argv[1:] if argv is None else argv) + ["--seconds", "0", "--trace", "0", "--checks-only"])
    finally:
        checks.model_checks = model_checks


if __name__ == "__main__":
    sys.exit(main())
