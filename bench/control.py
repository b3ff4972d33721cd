"""Readings that the limits of ``correct`` are set from, on the chip.

    python bench/control.py --workload fhp2-flow.lattice \
        --seeds 101,102,103 --seconds 5

For each seed, one whole run of the cell (set-up, a short window, the
comparison), then the control: the plain reference with one guarantee of
the configuration broken, put in the program's place and compared in the
same way.  Prints, per number compared, the largest reading of the
program (the lower reading) and the smallest of the control (the upper
reading).  The benchmark's own runs never run the control.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    lower, upper = {}, {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        run = harness.parse(["--workload", args.workload, "--seed",
                             str(seed), "--seconds", str(args.seconds)])
        result = harness.run_cell(run, control=True)
        out = result.pop("_outcome")
        program = {c.name: c.value for c in out.checks}
        print(json.dumps({"seed": seed, "correct": result["correct"],
                          "program": program, "control": out.control}),
              flush=True)
        for k, v in program.items():
            lower[k] = max(lower.get(k, v), v)
        for k, v in out.control.items():
            upper[k] = min(upper.get(k, v), v)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
