"""perfbench: one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process on a machine with the cell's chips.  Without a TPU it exits
non-zero and prints no result.  ``--rehearse`` (with ``JAX_PLATFORMS=cpu``)
walks every phase at 16^3 and always exits non-zero.  The last line of
standard output is the result object; everything else goes to standard
error or to ``perfbench_out/<cell>/``.
"""
import time

T_PROC0 = time.perf_counter()    # the interpreter's own start-up is not in

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="16^3 walk of every phase off the chip; always "
                         "exits non-zero")
    ap.add_argument("--control", choices=("bf16",),
                    help="also put the reference in this lower precision in the "
                         "program's place and read the same numbers (the "
                         "control; such a run prints no result)")
    ap.add_argument("--trace-sample", dest="trace_sample",
                    help="also write a small cut of the trace here")
    args = ap.parse_args()
    if args.rehearse and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit("perfbench: --rehearse needs JAX_PLATFORMS=cpu")
    if not args.rehearse and os.environ.get(
            "JAX_PLATFORMS", "").strip().lower() == "cpu":
        sys.exit("perfbench: JAX_PLATFORMS=cpu: not a chip run")

    from perfbench import harness

    result = harness.run(args, T_PROC0)
    line = json.dumps(result)
    tail = " ".join(f"{k}={v['value']:.6g}/limit={v['limit']:g}"
                    for k, v in result["compared"].items())
    print(f"[perfbench] correct={result['correct']} {tail}",
          file=sys.stderr, flush=True)
    if args.rehearse or args.control:
        print(f"[perfbench] not a result: {line}", file=sys.stderr,
              flush=True)
        sys.exit(4)
    print(line, flush=True)


if __name__ == "__main__":
    main()
