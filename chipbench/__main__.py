"""``python3 -m chipbench --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell; the last line of standard output is
the result. Without an accelerator: a non-zero exit and no result."""
import time

T_START = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench", description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if ":" in args.workload:
        ap.error("a benchmark run takes a cell of BENCHMARK.json")

    from chipbench import harness

    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    line, _ = harness.run(bench, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
