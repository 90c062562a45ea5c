"""``calibrate.py`` for a cell of the MLA / routed-expert family with a
multi-stream residual path: the same two readings (program and float8
control, many seeds in one process), read by this configuration's reference.

    python3 benchmarks/calibrate_mhc_mla_moe.py --workload <cell> --seeds ... \
        [--control-seeds 3] [--seconds 8] [--out chiprun_out/calibrate.jsonl]

As ``calibrate_mla_moe.py``: ``calibrate.py`` reads
``benchmarks.reference.served_gaps`` by that name; until a `benchmark` PR
makes it ask the cell's adapter for its reference, this file puts this
configuration's in that place for the length of the call. Not part of a
benchmark run, and never run by the driver.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    sys.path.insert(0, ROOT)
    from benchmarks import calibrate, reference, reference_mhc_mla_moe

    reference.served_gaps = reference_mhc_mla_moe.served_gaps
    return calibrate.main()


if __name__ == "__main__":
    sys.exit(main())
