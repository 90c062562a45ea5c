#!/bin/sh
# First contact of a cell with the chip: a cold run, then the limits'
# readings (program and control on 12 seeds in one process); with "sets" as a
# second argument, one set of six warm runs and a traced run between them.
# Everything is kept under chiprun_out/.
#   chiprun --timeout 3000 -- sh benchmarks/chip_first_contact.sh <cell> [sets]
cell=$1
mkdir -p chiprun_out
python3 benchmarks/run.py --workload "$cell" --seed 2147483700 --seconds 20 --trace 0 > "chiprun_out/first_cold_$cell.out" 2> "chiprun_out/first_cold_$cell.err"
echo "cold rc=$?"; grep -v "^[WEI]0000" "chiprun_out/first_cold_$cell.out" | grep -v "^requests:" | tail -8 | cut -c1-1500; tail -5 "chiprun_out/first_cold_$cell.err" | cut -c1-600
if [ "$2" = sets ]; then
python3 benchmarks/measure.py --workload "$cell" --seeds 2147485001,2147485002,2147485003,3000002004,3000002005,3000002006 --out "chiprun_out/first_set_$cell.jsonl"
python3 benchmarks/run.py --workload "$cell" --seed 2147483701 --seconds 50 --trace 1 > chiprun_out/first_trace.out 2> chiprun_out/first_trace.err
echo "trace rc=$?"; tail -8 chiprun_out/first_trace.out | cut -c1-6000; tail -5 chiprun_out/first_trace.err | cut -c1-600
fi
python3 benchmarks/calibrate.py --workload "$cell" --seeds 2147484801,2147484802,2147484803,2147484804,2147484805,2147484806,3000001807,3000001808,3000001809,3000001810,3000001811,3000001812 --control-seeds 12 --seconds 25 --out "chiprun_out/calibrate24_$cell.jsonl" 2> chiprun_out/calibrate.err | cut -c1-400
tail -3 chiprun_out/calibrate.err | cut -c1-500
