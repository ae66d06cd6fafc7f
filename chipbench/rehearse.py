"""Rehearse before the first chip call (no chip time, never a device number).

    python3 -m chipbench.rehearse            # every cell of BENCHMARK.json
    python3 -m chipbench.rehearse --cells gptj6b_ppo_hh --no-compile

For each cell, in a child process of its own (this parent stays off JAX):

1. the cell end to end on the CPU at the configuration's toy widths through
   the same code path (``run.py --rehearse``), a four-chip cell on four
   virtual devices;
2. the cell's generate, score and train-step programs compiled at the
   published widths for a described ``v5e:2x2`` (the TPU compiler is
   installed here and compiles for a chip that is not attached), with
   ``memory_analysis()`` printed: what the compiler refuses here costs no
   chip time, and argument + output + temp bytes say whether the depth fits.
"""

import argparse
import json
import os
import subprocess
import sys


def run_child(args, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    proc = subprocess.run([sys.executable, "-m"] + args, env=env, capture_output=True, text=True)
    return proc.returncode, proc.stdout, proc.stderr


def walk_cell(cell) -> bool:
    flags = f"--xla_force_host_platform_device_count={cell['chips']}"
    ok = True
    for trace in (0, 1):
        rc, out, err = run_child(
            ["chipbench.run", "--workload", cell["name"], "--seed", "3000000019",
             "--seconds", "2", "--trace", str(trace), "--rehearse"],
            {"XLA_FLAGS": flags},
        )
        last = out.strip().splitlines()[-1] if out.strip() else ""
        try:
            line = json.loads(last)
            good = rc == 0 and line["correct"] and line["device"]["platform"] == "cpu"
        except (ValueError, KeyError):
            line, good = None, False
        print(f"[walk] {cell['name']} trace={trace}: rc={rc} "
              f"{'ok' if good else 'FAILED'} {json.dumps(line)[:400] if line else err[-1500:]}",
              flush=True)
        ok &= good
    return ok


def compile_cell_child(name: str, depth: int = 0) -> int:
    """Runs in the child: abstract trainer at published widths, its mesh made
    of described v5e devices, the three programs lowered and compiled."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import time

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding

    from chipbench import job
    from trlx_tpu import perf
    from trlx_tpu.ops.sampling import GenerationConfig
    from trlx_tpu.parallel.mesh import make_mesh, set_global_mesh
    from trlx_tpu.parallel.sharding import batch_spec, fit_spec, param_shardings
    from trlx_tpu.trainer.base import _optimizer_state_shardings

    jax.config.update("jax_enable_compilation_cache", False)
    cell = job.find_cell(name)
    config_file = job.load_config(cell["config"])
    traffic = job.load_json("traffic", cell["traffic"])
    if depth:  # sizing: what would this cell need at another depth?
        config_file["job"]["model"]["model_extra_kwargs"]["num_layers"] = depth
        for key, field in config_file["maps"].items():
            if field == "num_layers":
                config_file["published"][key] = depth
        name = f"{name}@depth{depth}"
    cfg = job.build_config(config_file, traffic, 0, toy=False, ckpt_dir="/nonexistent")
    job.check_published_widths(cfg, config_file)
    shape = job.cycle_shape(cfg, traffic)

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = topo.devices[: cell["chips"]]
    jax.default_backend = lambda: "tpu"  # attention_impl auto and the kernels answer as on the chip
    trainer = perf._build_abstract_trainer(cfg)
    mesh = trainer.mesh = make_mesh(cfg.parallel, devices=devices)
    set_global_mesh(mesh)
    SDS = jax.ShapeDtypeStruct

    def attach(tree, shardings):
        return jax.tree_util.tree_map(lambda s, sh: SDS(s.shape, s.dtype, sharding=sh), tree, shardings)

    def batch(shape_, dtype):
        spec = fit_spec(mesh, shape_, tuple(batch_spec(len(shape_))))
        return SDS(shape_, dtype, sharding=NamedSharding(mesh, spec))

    params = attach(trainer.state.params, param_shardings(trainer.state.params, mesh))
    ref = attach(trainer.ref_params, param_shardings(trainer.ref_params, mesh))
    P, N, B = shape["prompt"], shape["new"], int(cfg.method.chunk_size)

    def report(label, lowered):
        t = time.time()
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        row = {k: int(getattr(mem, k)) for k in
               ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
                "alias_size_in_bytes")}
        row["total_gib"] = round((row["argument_size_in_bytes"] + row["output_size_in_bytes"]
                                  + row["temp_size_in_bytes"] - row["alias_size_in_bytes"]) / 2**30, 3)
        row["flash_kernel"] = "tpu_custom_call" in compiled.as_text()
        row["compile_s"] = round(time.time() - t, 1)
        print(f"[compile] {name} {label}: {json.dumps(row)}", flush=True)

    with mesh:
        gen_config = GenerationConfig.from_gen_kwargs(
            dict(trainer.generate_kwargs), eos_token_id=trainer.tokenizer.eos_token_id,
            pad_token_id=trainer.tokenizer.pad_token_id)
        report("generate", trainer._get_generate_fn(gen_config, ()).lower(
            trainer._engine_params(params), batch((B, P), np.int32), batch((B, P), np.int32),
            jax.random.PRNGKey(0)))
        report("score", trainer._get_score_fn((B, P, N)).lower(
            params, ref, batch((B, P + N), np.int32), batch((B, P), np.int32),
            batch((B, N), np.int32), batch((B, N), np.int32)))
        tb = perf._train_batch_sds(type(trainer).__name__.lower(), shape["batch"], P, N)
        tb = {k: batch(v.shape, v.dtype) for k, v in tb.items()}
        import dataclasses

        opt = attach(trainer.state.opt_state,
                     _optimizer_state_shardings(mesh, params, trainer.state.opt_state))
        from jax.sharding import PartitionSpec

        everywhere = NamedSharding(mesh, PartitionSpec())
        state = dataclasses.replace(
            trainer.state, params=params, opt_state=opt,
            step=SDS((), np.int32, sharding=everywhere),
            rng=SDS(trainer.state.rng.shape, trainer.state.rng.dtype, sharding=everywhere))
        report("train_step", trainer._build_train_step().lower(state, tb, SDS((), np.float32)))

    leaves = jax.tree_util.tree_leaves
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in
               leaves(trainer.state.params) + leaves(trainer.ref_params)
               + leaves(trainer.state.opt_state)) / cell["chips"]
    n_params = sum(int(np.prod(x.shape)) for x in leaves(trainer.state.params))
    print(f"[compile] {name} parameters={n_params/1e9:.3f}B held per chip (params + reference "
          f"branch + optimizer)={held/2**30:.2f} GiB", flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", nargs="*")
    ap.add_argument("--no-walk", action="store_true")
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--compile-child", help=argparse.SUPPRESS)
    ap.add_argument("--depth", type=int, default=0,
                    help="with --compile-child: size the cell at another depth")
    args = ap.parse_args()
    if args.compile_child:
        return compile_cell_child(args.compile_child, args.depth)

    from chipbench import job

    cells = [c for c in job.load_benchmark()["workloads"]
             if not args.cells or c["name"] in args.cells]
    ok = True
    for cell in cells:
        if not args.no_walk:
            ok &= walk_cell(cell)
        if not args.no_compile:
            rc, out, err = run_child(
                ["chipbench.rehearse", "--compile-child", cell["name"]],
                # the abstract trainer first builds its mesh from CPU devices
                {"XLA_FLAGS": f"--xla_force_host_platform_device_count={cell['chips']}"})
            print(out.strip() or err[-3000:], flush=True)
            if rc != 0:
                print(f"[compile] {cell['name']} FAILED rc={rc}\n{err[-3000:]}", flush=True)
            ok &= rc == 0
    print("rehearsal", "passed" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
