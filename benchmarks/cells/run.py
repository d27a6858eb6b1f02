"""The chip benchmark of `CountService`: one cell per call.

    python3 benchmarks/cells/run.py --workload ngram_pmi.ingest \
        --seed 7 --seconds 20 --trace 0

Run from the root of a checkout.  The cell, its configuration, traffic mix,
limits and per-layer metrics are found by name from `BENCHMARK.json`.  The
last line of standard output is the result as one JSON object; the numbers
compared with the reference, each beside its limit, are the last lines of
standard error.  Without a TPU, or with fewer chips than the cell needs, it
exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = pathlib.Path.cwd().resolve()
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the configuration's control counter in place "
                         "of its own (a run that must come out not correct)")
    args = ap.parse_args(argv)
    import harness
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    out = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                           bool(args.trace), root=ROOT, t_start=T_START,
                           control=args.control)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
