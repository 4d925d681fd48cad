"""Readings that the check's limits are set from.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

Runs the cell once per seed, in one process, exactly as ``run.py``
does, and for each run prints one JSON line with two readings over the
same sample of finished requests:

* ``program``: the widest gap by which a served token's float32
  reference logit lies below the reference's best (what ``run.py``
  holds to the limit);
* ``control``: the same gap for the token that the family's control
  (the dense family's: the reference with every matmul operand in
  float8) puts first at each position of the same prompts and served
  tokens.

The limit lies above the program's readings and below the control's.
``--smoke`` runs the configuration's small CPU widths with the kernels'
reference forms.
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(root: Path, workload: str, seed: int, seconds: float, *,
             smoke: bool = False, t_process: float = None) -> dict:
    """One run of the cell, with the program's and the control's widest
    gaps over the run's sample."""
    import harness
    import numpy as np

    out = {}
    plain_check = harness.check

    def check(fam, conf, mix, params, finished, seed, prefix_cache, max_len,
              log):
        checks, ok = plain_check(fam, conf, mix, params, finished, seed,
                                 prefix_cache, max_len, log)
        vocab = fam.shapes(conf)["V"]
        picked = harness.sample(finished, seed, int(mix["check"]["sample"]))
        out["control"] = max(
            float(np.max(harness.gaps_of(fam, conf, params, r, vocab,
                                         prefix_cache, max_len,
                                         control=True)))
            for r in picked)
        return checks, ok

    harness.check = check
    try:
        t = time.perf_counter() if t_process is None else t_process
        res = harness.run(root, workload, seed, seconds, False, t_process=t,
                          smoke=smoke)
    finally:
        harness.check = plain_check
    out.update(workload=workload, seed=seed,
               program=res["checks"]["max_logit_gap"]["value"],
               limit=res["checks"]["max_logit_gap"]["limit"],
               correct=res["correct"],
               tokens_per_s=res["metrics"].get("output_tok_per_s",
                                               {}).get("value"))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT / "bench"))
    for seed in args.seeds:
        t = time.perf_counter()
        r = readings(ROOT, args.workload, seed, args.seconds,
                     smoke=args.smoke, t_process=t)
        r["wall_s"] = time.perf_counter() - t
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
