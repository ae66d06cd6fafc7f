"""Measure the tolerances of ``correct``, with a control.

    python3 -m chipbench.seedcheck --cell <name> [--seeds 8] [--faults no_rotary strict_causal]

Runs checks 1-3 alone (``run.py --checks-only``: set-up, the warm-up cycle,
no window) on ``--seeds`` seeds, each in a process of its own (this parent
stays off JAX), then once per planted fault. Prints, for each tolerance, the
worst of the seeds, the mildest planted fault, and the band a tolerance may
lie in: at least three times above the first, at least three times below
the second. If the band is empty the statistic is wrong: change it, do not
widen it. Results also go to ``chiprun_out/seedcheck_<cell>.json``.
"""

import argparse
import json
import os
import subprocess
import sys

SEEDS = (11, 2147483659, 3000000019, 77001, 1234567891, 42, 2999999929, 600613)
STATS = ("logits_rel_l2", "decode_logprob_mad", "initial_sqrt_kl")


def one(cell, seed, fault, rehearse):
    cmd = [sys.executable, "-m", "chipbench.run", "--workload", cell, "--seed", str(seed),
           "--seconds", "0", "--trace", "0", "--checks-only"]
    if fault:
        cmd += ["--fault", fault]
    if rehearse:
        cmd += ["--rehearse"]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    lines = proc.stdout.strip().splitlines()
    try:
        line = json.loads(lines[-1])
    except (IndexError, ValueError):
        print(proc.stderr[-3000:], flush=True)
        return {"seed": seed, "fault": fault, "rc": proc.returncode, "error": True}
    row = dict(line["control"]["check_values"], seed=seed, fault=fault, rc=proc.returncode,
               correct=line["correct"], setup_s=line["metrics"]["setup_s"]["value"],
               peak_gib=line["metrics"]["peak_hbm_gib"]["value"])
    for text in lines[:-1]:
        if text.startswith('{"eos_shaping"') or text.startswith('{"length_report"'):
            row.update(json.loads(text))
    print(json.dumps(row), flush=True)
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--faults", nargs="*", default=["no_rotary", "strict_causal"])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    rows = [one(args.cell, s, None, args.rehearse) for s in SEEDS[: args.seeds]]
    planted = [one(args.cell, SEEDS[0], f, args.rehearse) for f in args.faults]
    summary = {}
    for stat in STATS:
        clean = [r[stat] for r in rows if stat in r]
        worst = max(clean) if clean else None
        faulty = [r[stat] for r in planted if stat in r]
        summary[stat] = {"worst_of_seeds": worst, "seeds": clean, "planted": faulty,
                         "tolerance_at_least": None if worst is None else 3 * worst,
                         "tolerance_at_most": min(faulty) / 3 if faulty else None}
    out = {"cell": args.cell, "rows": rows, "planted": planted, "summary": summary}
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/seedcheck_{args.cell}.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"summary": summary}, indent=1), flush=True)
    return 0 if all(not r.get("error") for r in rows + planted) else 1


if __name__ == "__main__":
    sys.exit(main())
