"""A KDA layer's cached state and conv rows against the reference's recurrence, on the chip.

    python3 -m chipbench.kda_state_check --workload kimilinear48b_ppo_ctx4k_r32 --seed <n> [--fault bf16_state]

``chipbench/state_check.py``'s reading (cell 10's) for the layers of
``ops/delta_rule.py``: ``run.py --checks-only`` (set-up, the warm-up cycle,
checks 1 to 3 on the collection that follows) with one more reading taken
where check 1 takes its own, on the same rows of the same rollouts. The
sampler's two calls into the model (``ops/sampling.py::generate``: a prefill
of the prompt's slots from slot 0 into ``make_kv_cache``'s cache of the
cell's own slots, then one token a step at the next slot) are made again on
the prompt and the first ``checks.RESP`` sampled tokens, the sampled rows
TOGETHER, so that the prefill runs ``KDAMixer``'s pieces of two rows as the
cell's does (4 rows of 3072 slots pass ``KDA_MAX_TOKENS``). Both leaves a KDA
layer's cache then holds are compared with the reference's ``kda_states``
(float32, ``highest``, token by token) OF THE INPUTS THAT LAYER SAW in the
system (``ln_attn``'s output, kept by ``capture_intermediates``), so that the
reading is of the mixer's projections, convs, norms, gates and recurrence
alone:

- ``kda_state_rel_l2_prefill`` / ``_decode``: the ``state`` leaf after the
  prefill (``kda_chunked``'s last carry) and after the steps (``kda_step``'s);
- ``kda_conv_rel_l2_prefill`` / ``_decode``: the ``conv`` leaf, the last rows
  of ``[q~ | k~ | v~]`` in front of the convs,

each the relative L2 distance over one row's leaf of one layer, the worst row
of the worst layer. A clean run reads the controls beside them, against the
same system leaves (``control``): the reference's state rounded to bfloat16
after every token (its ``bf16_state``: the control of the state's stated
float32, which checks 1 and 2 cannot fail,
``tolerances/kimi-linear-48b-a3b-l8e32.json``) and its conv rows rounded to
``float8_e4m3fn``, the nearest precision below the leaf's stated bfloat16.
``control_correct`` has to come out false, with no reading inside its limit
(``control_inside`` empty). ``--fault`` plants a fault in the reference as
``run.py`` does and reads no control.

The limits are the configuration's ``state_check`` in
``chipbench/tolerances/<config>.json``; the line ``{"kda_state_check": ...}``
carries ``state_correct``. Like ``state_check.py`` it decides nothing in
``run.py``: ``correct`` is checks 1 to 4 alone, and ``checks.py`` takes no
family's own deciding check (PERF.md, Open questions, has the edit that
would). A reference without ``kda_states`` has none to compare and the run is
``--checks-only`` as it was.
"""

import importlib
import sys

import numpy as np

from chipbench import checks, run
from chipbench.state_check import _layer_inputs, load_limits, verdict

LEAVES = ("state", "conv")
CONTROLS = {"state": "bf16_state", "conv": "float8_e4m3fn rows"}


def state_readings(trainer, config_file, gen_out, fault=None):
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.transformer import make_kv_cache

    ref = importlib.import_module(f"chipbench.reference.{config_file['family']}")
    if not hasattr(ref, "kda_states"):
        return {}
    rows = checks.sample_rows(int(gen_out.sequences.shape[0]))
    P = int(gen_out.prompt_mask.shape[1])
    N = int(gen_out.response_tokens.shape[1])
    R = min(checks.RESP, N)
    T = P + R
    seqs = np.asarray(jax.device_get(gen_out.sequences))[rows, :T]
    p_mask = np.asarray(jax.device_get(gen_out.prompt_mask))[rows].astype(np.int32)
    mask = np.concatenate([p_mask, np.asarray(jax.device_get(gen_out.response_mask))[rows, :R]], axis=1).astype(np.int32)
    params, module, tcfg = trainer.state.params, trainer.module, trainer.tcfg
    keep = dict(capture_intermediates=lambda mdl, method: mdl.name == "ln_attn", mutable=["intermediates"])

    @jax.jit
    def sampler_leaves(p, ids, m):
        """``generate``'s calls, with the sampled tokens fed back in."""
        slots = jnp.concatenate([m[:, :P], jnp.zeros((ids.shape[0], N), jnp.int32)], axis=1)
        out, seen = module.apply({"params": p}, ids[:, :P], attention_mask=slots, positions=None,
                                 cache=make_kv_cache(tcfg, ids.shape[0], P + N), cache_index=jnp.asarray(0, jnp.int32),
                                 logits_span=(P - 1, P), **keep)
        cache, seen = out["cache"], _layer_inputs(seen["intermediates"])
        prompt_len = jnp.sum(m[:, :P], axis=1).astype(jnp.int32)
        leaves = lambda c: {i: {name: layer[name] for name in LEAVES} for i, layer in enumerate(c) if "state" in layer}

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

    def rel(a, b):  # one row's leaf of one layer
        a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
        return float(jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b**2)))

    held, after, inputs = sampler_leaves(params, jnp.asarray(seqs), jnp.asarray(mask))
    if not held:
        return {}
    backbone, dims, at = checks.backbone_of(params), config_file["published"], (P - 1, T - 1)
    want = {None: ref.kda_states(backbone, dims, inputs, mask, at, fault=fault)}
    if fault is None:  # the controls: the same recurrence in the nearest precision below each leaf's own
        rounded = ref.kda_states(backbone, dims, inputs, mask, at, fault="bf16_state")
        want["control"] = {i: (rounded[i][0], want[None][i][1].astype(jnp.float8_e4m3fn).astype(jnp.float32))
                           for i in rounded}

    def readings(by_layer):
        out = {}
        for n, leaf in enumerate(LEAVES):
            for k, (when, got) in enumerate((("prefill", held), ("decode", after))):
                worst = {i: max(rel(got[i][leaf][r], by_layer[i][n][k, r]) for r in range(rows.size)) for i in by_layer}
                out[f"kda_{leaf}_rel_l2_{when}"] = max(worst.values())
                out[f"kda_{leaf}_rel_l2_{when}_layers"] = [worst[i] for i in sorted(worst)]
        return out

    out = {"kda_layers": sorted(held), "kda_rows": int(rows.size), "kda_steps": R, **readings(want[None])}
    if "control" in want:
        out["control"] = dict(readings(want["control"]), of=CONTROLS)
    return out


def main(argv=None) -> int:
    model_checks = checks.model_checks

    def with_states(trainer, config_file, gen_out, fault=None):
        values = state_readings(trainer, config_file, gen_out, fault=fault)
        if values:
            limits = load_limits(config_file["name"])
            said = dict(kda_state_check=values, limits=limits, state_correct=verdict(values, limits))
            if "control" in values:  # has to come out false, and by every limit
                said.update(control_correct=verdict(values["control"], limits),
                            control_inside=[k for k, v in limits.items() if values["control"].get(k, np.inf) <= v])
            run.say(**said)
        return model_checks(trainer, config_file, gen_out, fault=fault)

    checks.model_checks = with_states
    try:
        return run.main(list(sys.argv[1:] if argv is None else argv) + ["--seconds", "0", "--trace", "0", "--checks-only"])
    finally:
        checks.model_checks = model_checks


if __name__ == "__main__":
    sys.exit(main())
