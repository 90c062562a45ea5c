"""python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json on the machine it is started on. The
last line of standard output is the result; without a TPU, or outside a
checkout that holds the program, it exits non-zero and prints none.
"""

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    # The checkout's own program, never an installed copy of it.
    if not os.path.isdir(os.path.join(ROOT, "tritonclient_tpu")):
        print(f"{ROOT} holds no tritonclient_tpu: nothing to measure",
              file=sys.stderr)
        return 1
    sys.path.insert(0, ROOT)
    import tritonclient_tpu

    if not os.path.abspath(tritonclient_tpu.__file__).startswith(ROOT + os.sep):
        print("tritonclient_tpu was imported from outside this checkout",
              file=sys.stderr)
        return 1

    from benchmarks import harness, spec

    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), process_start=_PROCESS_START)
    except (harness.BenchmarkError, spec.SpecError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
