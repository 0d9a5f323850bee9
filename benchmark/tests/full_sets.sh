#!/bin/bash
# Two sets of N runs of one cell (6 unless told), the same seeds in both sets,
# as the contract's rule for a bound asks; one extra first run warms the compile
# cache and is reported apart; one traced run last.  Each run's whole output is
# kept in chiprun_out/sets/<cell>.log, its result line in <cell>.<set>.jsonl
#   bash benchmark/tests/full_sets.sh <workload> <seconds> [seed0] [runs per set]
W=$1; S=$2; SEED0=${3:-2147480000}; N=${4:-6}
mkdir -p chiprun_out/sets
: > chiprun_out/sets/$W.log
run() { python3 benchmark/run.py --workload "$W" --seed "$1" --seconds "$S" --trace "${2:-0}" 2>>chiprun_out/sets/$W.err | tee -a chiprun_out/sets/$W.log | tail -n 1; }
run $((SEED0 + 99)) > chiprun_out/sets/$W.first.jsonl
for SET in 1 2; do
  : > chiprun_out/sets/$W.set$SET.jsonl
  for K in $(seq 1 $N); do run $((SEED0 + K * 7919)) >> chiprun_out/sets/$W.set$SET.jsonl; done
done
python3 benchmark/tests/spread.py chiprun_out/sets/$W
run $((SEED0 + 5)) 1 > chiprun_out/sets/$W.trace.jsonl
cut -c1-3000 chiprun_out/sets/$W.trace.jsonl
