"""A cell's control or fault on the chip, at the cell's own size:
`python3 -m benchmark.tests.control --workload W --fault F --seed N
--seconds S` runs the cell with benchmark/tests/faulty_serve.py in the
server's place and prints `correct` and the numbers compared.  Exit
code 0 when `correct` came out false, as it must."""

from __future__ import annotations

import argparse
import json
import sys

from benchmark import run
from benchmark.server import RunFailure


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    try:
        result = run.run_cell(
            args.workload, args.seed, args.seconds, False,
            rehearsal=args.rehearse_cpu,
            launcher=[sys.executable, "-m", "benchmark.tests.faulty_serve"],
            extra_env={"BENCHMARK_FAULT": args.fault})
    except RunFailure as e:
        # a control that crashes has failed, and sets no upper reading
        print(json.dumps({"fault": args.fault, "crashed": str(e)[-600:]}))
        return 0
    print(json.dumps({"workload": args.workload, "fault": args.fault,
                      "seed": args.seed, "correct": result["correct"],
                      "attempted": result["attempted"],
                      "failed": result["failed"],
                      "compared": result["compared"]}))
    return 0 if not result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
