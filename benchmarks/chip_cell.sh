#!/bin/sh
# A cell's readings in one call: the two sets of six runs the bounds are set
# from, and three traced runs.
#   chiprun --timeout 3000 -- sh benchmarks/chip_cell.sh <cell> [sets]
cell=$1; sets=${2:-2}
mkdir -p chiprun_out
python3 benchmarks/measure.py --workload "$cell" --sets "$sets" --seeds 2147485001,2147485002,2147485003,3000002004,3000002005,3000002006 --out "chiprun_out/sets_$cell.jsonl"
python3 benchmarks/measure.py --workload "$cell" --trace 1 --seeds 2147485101,2147485102,3000002103 --out "chiprun_out/traced_$cell.jsonl" | cut -c1-2500
