#!/usr/bin/env python
"""CI lint entry point: run EVERY graftlint pass (metric-names included)
over the real ``trlx_tpu/`` tree AND ``scripts/`` (tooling spawns
processes and writes files — unlinted tooling is where the
"works on my launcher" hangs hide) against the committed baseline
(``GRAFTLINT_BASELINE.txt``). Non-zero exit on any non-baselined finding
or stale baseline entry.

``--sarif PATH`` additionally writes a SARIF 2.1.0 document (findings +
stale entries + parse errors) so CI can annotate them inline on the PR;
the human rendering stays on stdout either way.

Wired into the fast test tier as the self-run in ``tests/test_analysis.py``
— ``pytest tests/`` fails when the tree regresses, making the linter a
standing CI gate (docs/STATIC_ANALYSIS.md).
"""

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from trlx_tpu.analysis import main  # noqa: E402

SCAN_ROOTS = ("trlx_tpu", "scripts")

# flags that consume the next argv element (so positional detection below
# doesn't mistake their values for scan roots)
_VALUE_FLAGS = {"--baseline", "--select", "--format", "--output", "--sarif"}


def run(argv=None) -> int:
    argv = list(argv) if argv is not None else []
    out: list = []
    positionals = 0
    i = 0
    while i < len(argv):
        arg = argv[i]
        if arg == "--sarif" or arg.startswith("--sarif="):
            if "=" in arg:
                path = arg.split("=", 1)[1]
                i += 1
            elif i + 1 < len(argv):
                path = argv[i + 1]
                i += 2
            else:
                print("lint.py: --sarif needs a path", file=sys.stderr)
                return 2
            out += ["--format", "sarif", "--output", path]
            continue
        if arg in _VALUE_FLAGS and i + 1 < len(argv):
            out += [arg, argv[i + 1]]
            i += 2
            continue
        if not arg.startswith("-"):
            positionals += 1
        out.append(arg)
        i += 1
    if positionals == 0:
        out = [os.path.join(REPO_ROOT, r) for r in SCAN_ROOTS] + out
    has_baseline = any(
        a in ("--baseline", "--no-baseline") or a.startswith("--baseline=")
        for a in out
    )
    if not has_baseline:
        out += ["--baseline", os.path.join(REPO_ROOT, "GRAFTLINT_BASELINE.txt")]
    return main(out)


if __name__ == "__main__":
    sys.exit(run(sys.argv[1:]))
