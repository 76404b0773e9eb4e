"""The control of the check that decides ``correct``: the plain reference,
put in the program's place with one stated guarantee broken, must come
out as not correct.

    python3 benchmark/control.py --workload g500-s20.single \\
        --seeds 11,12,13 --seconds 5

The configurations guarantee exact answers.  The control comes from the
driver that the cell's traffic mix names: its ``control()`` breaks that
guarantee the way a change that trades exactness for speed would (for
``closed_search``, each search stops one level short of its last level,
as a level cap set too low would).

Each seed runs the whole cell (set-up, a short window at the cell's own
load, the check) with the control in the program's place, and prints the
run's result line.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def main(argv=None) -> int:
    from benchmark import run as bench_run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        bench = harness.load_benchmark()
        _, _, params = harness.load_cell(bench, args.workload)
        swap = harness.load_plugin("drivers", params["driver"]).control()
        for seed in (int(s) for s in args.seeds.split(",")):
            run_args = bench_run.parse([
                "--workload", args.workload, "--seed", str(seed),
                "--seconds", str(args.seconds)])
            result = bench_run.execute(run_args, t_start=time.perf_counter(),
                                       **swap)
            print(json.dumps({"control": args.workload, "seed": seed,
                              "correct": result["correct"],
                              "checks": result["checks"]}), flush=True)
    except harness.BenchError as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
