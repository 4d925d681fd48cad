"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout (it needs ``BENCHMARK.json`` and the
program under ``src``). The cell's configuration, traffic mix and
metrics are found by the names ``BENCHMARK.json`` gives them. With
``--trace 0`` the result reports the cell's end-to-end metrics; with
``--trace 1`` the window runs under JAX's profiler and the result
reports its per-layer metrics.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number the check compared,
with its limit); the last lines of standard error repeat the checks.
Without an accelerator, or with fewer chips than the cell asks for, it
prints no result and exits with code 2.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    # the TPU runtime's logs would otherwise go to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    try:
        out = harness.run(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_process=T_PROCESS)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
