"""One run of a benchmark cell, then the program store's guard on it.

    python3 scripts/verify_store.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The arguments are ``chipbench.run``'s and the run is its own, result line and
all. Afterwards every program the job loaded from the store
(``trlx_tpu/utils/programs.py``) is traced and lowered afresh and its StableHLO
digest compared with the one its entry recorded: the check a job never runs,
because it is the cost the store removes. The last line of standard output is
``{"store_verify": {...}}``; the exit code is 1 where an entry is stale.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from chipbench import run
    from trlx_tpu.utils import programs

    held = {}
    hook = run.Harness.hook

    def keeping(self, trainer):
        held["trainer"] = trainer
        return hook(self, trainer)

    run.Harness.hook = keeping
    rc = run.main(argv)
    trainer = held["trainer"]
    t0 = time.perf_counter()
    out = {"loaded": trainer.programs.loaded(), "stale": []}
    try:
        out["compared"] = programs.verify(trainer)
    except RuntimeError as e:
        out["stale"] = str(e).splitlines()[1:]
    out["seconds"] = round(time.perf_counter() - t0, 3)
    print(json.dumps({"store_verify": out}), flush=True)
    return rc or int(bool(out["stale"]))


if __name__ == "__main__":
    sys.exit(main())
