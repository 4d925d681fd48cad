"""Rate sweep of an open-loop cell, to find the knee its rate is set from.

    python3 bench/sweep.py --workload <cell> --seconds 30 --sojourn-s 30 --rates 2 2.5 3

Runs the cell once per rate, in one process and otherwise as ``run.py``
does, with the mix's arrival rate replaced and its in-flight warm-up
scaled to the rate (rate x ``--sojourn-s``, the seconds a request spends
in the engine). Prints one JSON line per rate with the end-to-end
metrics. The knee is the highest rate at which the tokens per second
still follow the offered load and the TTFT tail does not grow with the
window; a cell's rate is set below it, at about four fifths.
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sojourn-s", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT / "bench"))
    import harness

    load = harness.load_cell
    for rate in args.rates:
        def at_rate(root, name, rate=rate):
            cell = load(root, name)
            cell.mix["arrivals"] = dict(cell.mix["arrivals"], rate_rps=rate)
            cell.mix["warmup"] = dict(cell.mix["warmup"], in_flight=round(
                rate * args.sojourn_s))
            return cell

        harness.load_cell = at_rate
        t = time.perf_counter()
        out = harness.run(ROOT, args.workload, args.seed, args.seconds, False,
                          t_process=t)
        print(json.dumps({
            "rate_rps": rate, "attempted": out["attempted"],
            "correct": out["correct"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "wall_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
