"""One cell, once.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one JAX initialisation, nothing spawned. The job goes through
the program's normal path: a ``TRLConfig``, ``trlx_tpu.train()``, the prompt
pipeline, ``make_experience`` and the trainer's own learn loop. The harness
stands in for the tracker: the tracker sees every collection and step record
as it is logged, so it can time-stamp whole cycles (collect ``num_rollouts``,
then every optimizer step on them) and end the run at the first cycle
boundary past ``--seconds``, after one more collection on which checks 1 and
2 run. Diagnostics go to earlier lines; the LAST line of standard output is
the contract's one JSON object.

Where JAX finds no TPU the run fails at once and prints no result. The one
exception is ``--rehearse`` with ``JAX_PLATFORMS=cpu`` pinned by the caller:
the same code path at the configuration's toy widths, ``device`` says
``cpu`` and the line carries ``"rehearsal": true``. A number from such a run
is never a device number.
"""

import time

T_PROCESS_START = time.time()  # before any heavy import: set-up starts here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

from chipbench import checks, job  # noqa: E402

REPO = os.path.dirname(job.ROOT)
OUT_DIR = os.path.join(REPO, ".chipbench_out")  # inside the checkout, in .gitignore
TRACE_CYCLES = 1  # whole cycles under the profiler in a --trace 1 run


class StopRun(BaseException):
    """Raised from the tracker at the first cycle boundary past --seconds.
    The program has no stop predicate (PERF.md, Open questions)."""


def say(**kv):
    print(json.dumps(kv), flush=True)


class Harness:
    """The tracker object the trainer logs to, and the run's state."""

    def __init__(self, args, cell, config_file, traffic):
        self.args = args
        self.config_file = config_file
        self.traffic = traffic
        self.trainer = None
        self.shape = None
        self.captured_gen = None
        self.check_values = {}
        self.cycles = []         # whole cycles in the window
        self.open = None         # the cycle being filled
        self.warm = False
        self.t_window = None
        self.setup_s = None
        self.compile_events = 0  # jax backend compiles, counted all along
        self.compiles_at_window = None
        self.recompile_at_window = None
        self.recompile_last = {}
        self.fingerprint_before = None
        self.failed = 0
        self.attempted = 0
        self.profiling = False
        self.closing = False
        self.peak = 0

    # -- wiring ---------------------------------------------------------

    def hook(self, trainer):
        """``init_trainer_hook``: after the trainer is built, before the
        first collection."""
        self.trainer = trainer
        trainer.tracker = self
        self.shape = job.cycle_shape(trainer.config, self.traffic)
        if trainer.tokenizer.vocab_size > trainer.tcfg.vocab_size:
            raise ValueError(f"the traffic's tokenizer has {trainer.tokenizer.vocab_size} ids, "
                             f"the model's vocabulary {trainer.tcfg.vocab_size}")
        self._pin_learner_pad(trainer)
        if self.traffic.get("eos_rate"):
            from chipbench import shaping

            info = shaping.shape_eos(trainer, self.traffic, self.args.seed)
            say(eos_shaping=info)
        if self.args.trace:
            self._annotate(trainer)

    def _pin_learner_pad(self, trainer):
        """The trainer's own call leaves ``query_length``/``response_length``
        of ``store.create_loader`` unset, so each minibatch is padded to its
        own longest row and the train step compiles once per padded shape:
        with uneven lengths that is a compile in nearly every step. The
        harness pins both to the cell's maxima through the loader's own
        arguments (PERF.md, Open questions: a pad policy inside the program)."""
        create = trainer.store.create_loader
        P, N = self.shape["prompt"], self.shape["new"]

        def create_loader(*a, **kw):
            kw.setdefault("query_length", P)
            kw.setdefault("response_length", N)
            return create(*a, **kw)

        trainer.store.create_loader = create_loader

    def _capture_next_generate(self):
        """Keep the output of the next rollout generation: the sampler's own
        record of what it decoded (tokens, masks, behaviour logprobs), which
        the serial path otherwise drops inside ``make_experience``."""
        generate = self.trainer.generate

        def capturing(*a, **kw):
            out = generate(*a, **kw)
            if self.captured_gen is None:
                self.captured_gen = out
            return out

        self.trainer.generate = capturing

    def _annotate(self, trainer):
        """Host spans of the benchmark's own, around its calls into the
        program's layers, on the profiler's clock (--trace 1 only)."""
        import jax

        def wrap(obj, name, label):
            fn = getattr(obj, name)

            def wrapped(*a, **kw):
                with jax.profiler.TraceAnnotation(label):
                    return fn(*a, **kw)

            setattr(obj, name, wrapped)

        wrap(trainer, "make_experience", "chipbench/collect")
        wrap(trainer, "generate", "chipbench/generate")
        wrap(trainer, "_dispatch_score", "chipbench/score_dispatch")
        wrap(trainer, "reward_fn", "chipbench/reward")
        wrap(trainer, "train_step", "chipbench/train_step")
        wrap(trainer, "post_epoch_callback", "chipbench/post_epoch")

    # -- the tracker interface -----------------------------------------

    def log(self, stats, step=None):
        now = time.perf_counter()
        stats = dict(stats)
        if "time/exp" in stats:
            self._on_collection(stats)
        elif "time/train_step" in stats:
            self._on_step(stats, now)

    def finish(self):
        pass

    # -- cycles -----------------------------------------------------------

    def _on_collection(self, stats):
        if self.closing:
            self._model_checks_and_stop()
        if self.open is None:  # the first collection: before any update
            kl = stats.get("policy/sqrt_kl", stats.get("policy/sqrt_ref_kl"))
            self.check_values["initial_sqrt_kl"] = float("nan") if kl is None else float(kl)
            self.fingerprint_before = checks.leaf_fingerprints(self.trainer.state.params)
            self.open = {"start": None}
        self.open.update(collection=stats, steps=[], store_checked=False)

    def _close_window(self):
        """The window is over. Read what belongs to it (peak memory, compile
        counts) now; then let the loop run on into ONE more collection, whose
        generation is kept for checks 1 and 2. They run after the window and
        after the memory reading, because the float32 reference would
        otherwise set the peak that ``peak_hbm_gib`` reports."""
        import jax

        if self.profiling:
            self._stop_profiler()
        self.closing = True
        self.compiles_at_end = self.compile_events
        self.peak = peak_bytes(jax.devices())
        self._capture_next_generate()

    def _model_checks_and_stop(self):
        """Checks 1 and 2 on the collection that follows the window: its
        rollouts were sampled under the parameters the trainer holds now."""
        t = time.perf_counter()
        values = checks.model_checks(self.trainer, self.config_file, self.captured_gen,
                                     fault=self.args.fault)
        self.check_values.update(values)
        say(model_checks=values, seconds=round(time.perf_counter() - t, 3))
        raise StopRun()

    def _on_step(self, stats, now):
        cyc = self.open
        if cyc is None:  # a step with no collection before it: not expected
            raise RuntimeError("a train step was logged before any collection")
        if not cyc["store_checked"]:
            # the store was refilled between the collection record and this step
            cyc["store_checked"] = True
            elems = list(self.trainer.store.history)
            cyc["delivered"] = len(elems)
            cyc["failed"] = checks.store_failures(self.trainer, self.shape["rollouts"])
            cyc["lengths"] = [int(len(e.response_tensor)) for e in elems]
            cyc["row_lengths"] = [(int(len(e.query_tensor)), int(len(e.response_tensor)))
                                  for e in elems]
        cyc["steps"].append(stats)
        for k, v in stats.items():
            if k.startswith("recompile/"):
                self.recompile_last[k] = float(v)
        if len(cyc["steps"]) < self.shape["steps"]:
            return
        # ---- a cycle boundary ----
        cyc["end"] = now
        self.open = {"start": now}
        if not self.warm:
            self._end_of_warmup(cyc)
            return
        self.cycles.append(cyc)
        if self.profiling and len(self.cycles) >= TRACE_CYCLES:
            self._stop_profiler()
            self.open["start"] = time.perf_counter()
        if now - self.t_window >= self.args.seconds:
            self._close_window()

    def _end_of_warmup(self, cyc):
        """Every shape of the cell has now run once. What follows is timed."""
        self.warm = True
        self.compiles_at_window = self.compile_events
        self.recompile_at_window = dict(self.recompile_last)
        gc.collect()
        if self.args.trace:
            self._start_profiler()
        self.setup_s = time.time() - T_PROCESS_START
        self.t_window = time.perf_counter()
        self.open = {"start": self.t_window}
        say(warmup_done=True, setup_s=round(self.setup_s, 3),
            compiles_in_setup=self.compiles_at_window,
            warmup_collect_s=cyc["collection"].get("time/exp"),
            warmup_first_step_s=cyc["steps"][0].get("time/train_step"))
        if self.args.checks_only:
            # the warm-up cycle stands in for the window: its update is checked,
            # its times (compilation included) mean nothing
            cyc["start"] = cyc["end"] - 1.0
            self.cycles = [cyc]
            self._close_window()

    # -- profiler ---------------------------------------------------------

    def _start_profiler(self):
        import jax

        self.trace_dir = os.path.join(OUT_DIR, "trace")
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.profiling = True

    def _stop_profiler(self):
        import jax

        jax.profiler.stop_trace()
        self.profiling = False


def peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats()  # None where the backend keeps none (CPU)
        if stats:
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def count_compiles(harness):
    import jax.monitoring

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            harness.compile_events += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def end_to_end(h: Harness, chips: int, peak: int):
    seconds = sum(c["end"] - c["start"] for c in h.cycles)
    rollouts = sum(c["delivered"] for c in h.cycles)
    tokens = sum(sum(c["lengths"]) for c in h.cycles)
    return {
        "samples_per_s": {"value": rollouts / seconds / chips, "unit": "samples/s"},
        "gen_tokens_per_s": {"value": tokens / seconds / chips, "unit": "tokens/s"},
        "peak_hbm_gib": {"value": peak / 2**30, "unit": "GiB"},
        "setup_s": {"value": h.setup_s, "unit": "s"},
    }


def finish_checks(h: Harness) -> bool:
    v = h.check_values
    losses = [float(x) for c in h.cycles for s in c["steps"]
              for k, x in s.items() if k.startswith("losses/")]
    v["losses_finite"] = bool(losses) and bool(np.isfinite(losses).all())
    after = checks.leaf_fingerprints(h.trainer.state.params)
    v["leaf_changed"] = any(a != b for a, b in zip(h.fingerprint_before, after))
    delta = {k: x - h.recompile_at_window.get(k, 0.0) for k, x in h.recompile_last.items()}
    v["no_recompile"] = all(x == 0 for x in delta.values())
    v["no_recompile_detail"] = delta
    v["compiles_in_window"] = h.compiles_at_end - h.compiles_at_window
    v["no_compile_in_window"] = v["compiles_in_window"] == 0
    h.attempted = h.shape["rollouts"] * len(h.cycles)
    h.failed = sum(c["failed"] for c in h.cycles)
    v["rollouts_delivered"] = h.failed == 0 and h.attempted > 0
    return checks.verdict(v, checks.load_tolerances(h.config_file["name"]))


def length_report(h: Harness):
    """Realised response lengths of the window against the traffic file."""
    lengths = np.asarray([n for c in h.cycles for n in c["lengths"]])
    cap = h.shape["new"]
    out = {"rollouts": int(lengths.size), "mean": float(lengths.mean()),
           "at_cap_share": float((lengths >= cap).mean()),
           "histogram_64": np.bincount(np.minimum(lengths // 64, cap // 64),
                                       minlength=cap // 64 + 1).tolist()}
    r = h.traffic.get("eos_rate")
    if r:
        q = 1.0 - float(r)
        out["geometric_mean_predicted"] = (1.0 - q**cap) / (1.0 - q)
        out["geometric_at_cap_predicted"] = q ** (cap - 1)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default=None,
                    help="control run of the yardstick: plant this fault in the reference")
    ap.add_argument("--checks-only", action="store_true",
                    help="stop after the warm-up cycle: checks 1-3 alone, no window (seedcheck.py)")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU walk at toy widths; needs JAX_PLATFORMS=cpu pinned")
    args = ap.parse_args(argv)

    cell = job.find_cell(args.workload)
    traffic = job.load_json("traffic", cell["traffic"])

    # the program's own MFU gauge lowers and compiles the train step again on
    # a daemon thread after the first step, which would land in the window;
    # the benchmark counts FLOPs from shapes itself (flops.py)
    os.environ["TRLX_TPU_MFU"] = "0"

    import trlx_tpu
    from trlx_tpu.trlx import initialize_runtime, measurement_devices

    initialize_runtime()  # compile cache at <checkout>/.jax_cache unless placed from outside
    devices, on_cpu = measurement_devices()  # raises where JAX finds no TPU
    if on_cpu and not args.rehearse:
        raise SystemExit("JAX_PLATFORMS=cpu is pinned: a CPU run prints no result "
                         "(add --rehearse for the toy-width walk)")
    if args.rehearse and not on_cpu:
        raise SystemExit("--rehearse is the CPU walk: pin JAX_PLATFORMS=cpu")
    chips = int(cell["chips"])
    if len(devices) != chips:
        raise SystemExit(f"cell {cell['name']} asks for {chips} chip(s), JAX has {len(devices)}")

    from chipbench import peaks

    peak_row = None if on_cpu else peaks.lookup(devices[0].device_kind)  # unknown kind: error

    config_file = job.load_config(cell["config"], toy=on_cpu)
    os.makedirs(OUT_DIR, exist_ok=True)
    cfg = job.build_config(config_file, traffic, args.seed, toy=on_cpu,
                           ckpt_dir=os.path.join(OUT_DIR, "ckpts"))
    if not on_cpu:
        job.check_published_widths(cfg, config_file)

    h = Harness(args, cell, config_file, traffic)
    count_compiles(h)
    say(cell=cell["name"], config=config_file["name"], traffic=traffic["name"], seed=args.seed,
        device=devices[0].device_kind, chips=chips, rehearsal=on_cpu)
    try:
        trlx_tpu.train(
            reward_fn=job.make_reward_fn(int(getattr(cfg.method, "group_size", 1))),
            prompts=job.make_prompts(traffic, args.seed),
            eval_prompts=job.eval_prompt(traffic, args.seed),
            config=cfg,
            init_trainer_hook=h.hook,
        )
        raise RuntimeError("the learn loop ended by itself before --seconds")
    except StopRun:
        pass

    peak = h.peak
    correct = finish_checks(h)
    say(length_report=length_report(h))
    held = [float(s["moe/held_frac"]) for c in h.cycles for s in c["steps"] if "moe/held_frac" in s]
    say(cycles=[round(c["end"] - c["start"], 4) for c in h.cycles],
        window_s=round(sum(c["end"] - c["start"] for c in h.cycles), 4),
        **({"moe_held_frac_mean": float(np.mean(held))} if held else {}),
        peak_bytes=peak, peak_bytes_after_checks=peak_bytes(devices))

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": h.attempted, "failed": h.failed}
    if args.trace:
        from chipbench import layers

        metrics, breakdown, busy = layers.per_layer(h, cell, peak_row, chips)
        line["metrics"] = metrics
        device.update(busy)
        line["breakdown"] = breakdown
    else:
        line["metrics"] = end_to_end(h, chips, peak)
    line["device"] = device
    if on_cpu:
        line["rehearsal"] = True
    if args.checks_only or args.fault:
        line["control"] = {"checks_only": args.checks_only, "fault": args.fault,
                           "check_values": {k: v for k, v in h.check_values.items()
                                            if isinstance(v, (int, float, bool))}}
    # each number compared beside its limit: last in the line, and the last
    # lines of standard error
    line["compared"] = checks.compared(h.check_values,
                                       checks.load_tolerances(config_file["name"]))
    for name, row in line["compared"].items():
        print(f"compared {name}: {json.dumps(row)}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
