"""Find the highest arrival rate an open-loop cell sustains, on the chip.

    python bench/knee.py --workload fhp2-flow.sweep --rates 2,4,8 \
        --seconds 20 --seed 5

Runs the cell once per offered rate in one process (the traffic file's
``rate_per_s`` replaced) and prints, per rate, the tails, the engine's
round time and the backlog: the queue at the window's close and its
largest value, and the time the engine then took to drain.  A rate whose
backlog grows through the window is past the knee.  The cell's traffic
file then takes a fixed rate below the knee.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    for rate in [float(r) for r in args.rates.split(",")]:
        run = harness.parse(["--workload", args.workload, "--seed",
                             str(args.seed), "--seconds", str(args.seconds)])
        result = harness.run_cell(
            run, overrides={"traffic": {"rate_per_s": rate}})
        out = result.pop("_outcome")
        print(json.dumps({"rate_per_s": rate, "correct": result["correct"],
                          "attempted": result["attempted"],
                          "failed": result["failed"],
                          **{k: v for k, v in out.end_to_end.items()
                             if k != "setup_s"},
                          **out.readings["counts"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
