#!/usr/bin/env python
"""Thin shim: the metric-name lint now lives in the graftlint framework as
the ``metric-names`` pass (``trlx_tpu/analysis/conventions.py``,
docs/STATIC_ANALYSIS.md).

Kept so existing invocations (``python scripts/check_metric_names.py``) and
``tests/test_metric_names.py`` keep working unchanged — the public helpers
(``find_violations``/``scanned_keys``/``LEGACY_KEYS``/``RESILIENCE_KEYS``/
``ENGINE_KEYS``) re-export the framework implementations with identical
semantics. Prefer
``scripts/lint.py`` (all passes) going forward.
"""

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from trlx_tpu.analysis.conventions import (  # noqa: E402,F401
    ATTRIBUTION_KEYS,
    CLUSTER_KEYS,
    DIST_KEYS,
    ENGINE_KEYS,
    FLIGHTREC_KEYS,
    HEALTH_KEYS,
    LEARN_KERNEL_KEYS,
    LEGACY_KEYS,
    OBS_KEYS,
    RESILIENCE_KEYS,
    SERVE_KEYS,
    SETUP_KEYS,
    SETUP_SPAN_NAMES,
    _CONVENTION_RE,
    _KEY_RE,
    find_violations as _find_violations,
    scanned_keys as _scanned_keys,
)

SCAN_DIR = os.path.join(REPO_ROOT, "trlx_tpu")


def find_violations(scan_dir: str = SCAN_DIR):
    """All (relpath, lineno, key) whose key breaks the convention."""
    return _find_violations(scan_dir)


def scanned_keys(scan_dir: str = SCAN_DIR):
    """key → occurrence count over the tree."""
    return _scanned_keys(scan_dir)


def main(argv=None) -> int:
    violations = find_violations()
    if not violations:
        n = sum(scanned_keys().values())
        print(f"check_metric_names: OK ({n} stats[...] sites, all namespaced)")
        return 0
    print("check_metric_names: metric keys violating the namespace/name convention:")
    for relpath, lineno, key in violations:
        print(f"  {relpath}:{lineno}: stats[\"{key}\"]")
    print(
        f"\n{len(violations)} violation(s). New metrics must be namespaced "
        "(docs/OBSERVABILITY.md); LEGACY_KEYS is frozen."
    )
    return 1


if __name__ == "__main__":
    sys.exit(main())
