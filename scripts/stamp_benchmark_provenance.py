#!/usr/bin/env python
"""Stamp a backend/device_kind/toolchain provenance block into committed
``benchmarks/**/*.json`` artifacts that predate the convention.

New artifacts get their provenance embedded at measurement time
(``trlx_tpu.benchmark.provenance()``); this retrofits the already-committed
ones so no artifact in the tree is ambiguous about what produced it
(ROADMAP: bench falls back to CPU silently — a CPU-scale artifact must say
so on its face). Retrofitted blocks carry ``"retrofit": true`` and take the
backend from the artifact's own recorded ``backend`` field (never guessed);
``device_kind``/versions come from the current container toolchain, which
is the toolchain the committed CPU artifacts were produced under.

Usage: ``python scripts/stamp_benchmark_provenance.py [--check]``
(``--check`` exits 1 if any artifact is missing provenance, stamps nothing).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BENCH_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks"
)

# not a measurement artifact: budgets carry their own backend/device_kind/
# jax_version header
SKIP = {"perf_budgets.json"}


def main(argv=None) -> int:
    check_only = "--check" in (argv or sys.argv[1:])
    from trlx_tpu.trlx import initialize_runtime

    initialize_runtime()
    from trlx_tpu.benchmark import provenance

    missing = []
    for dirpath, _dirnames, filenames in os.walk(BENCH_DIR):
        for name in sorted(filenames):
            if not name.endswith(".json") or name in SKIP:
                continue
            path = os.path.join(dirpath, name)
            with open(path) as f:
                try:
                    artifact = json.load(f)
                except ValueError:
                    print(f"skip (not a JSON object): {path}")
                    continue
            if not isinstance(artifact, dict) or "provenance" in artifact:
                continue
            missing.append(path)
            if check_only:
                continue
            current = provenance()
            recorded = artifact.get("backend")
            # a retrofit block carries only what it can actually vouch for:
            # the artifact's own recorded backend and the container
            # toolchain. Run-specific fields (device_kind, num_devices,
            # timestamp) are included ONLY when the recorded backend
            # matches the stamping machine's — stamping, say, a TPU
            # artifact from a CPU box must not invent its device shape.
            block = {
                "backend": recorded or current["backend"],
                "jax_version": current["jax_version"],
                "python_version": current["python_version"],
                "retrofit": True,
                "stamped_at": current["timestamp"],
            }
            if recorded in (None, current["backend"]):
                block["device_kind"] = current["device_kind"]
            artifact["provenance"] = block
            with open(path, "w") as f:
                json.dump(artifact, f, indent=2)
                f.write("\n")
            print(f"stamped {path}")
    if check_only and missing:
        print("artifacts missing provenance:\n  " + "\n  ".join(missing))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
