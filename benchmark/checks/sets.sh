#!/bin/bash
# usage: sets.sh <workload> <seedbase>
W=$1; B=$2
mkdir -p chiprun_out/sets
for set in 1 2; do
  for i in 1 2 3 4 5 6; do
    s=$((B+i))
    python3 benchmark/run.py --workload $W --seed $s --seconds 51 --trace 0 > chiprun_out/sets/${W}_set${set}_${s}.out 2> chiprun_out/sets/${W}_set${set}_${s}.err
    echo "$W set$set seed $s rc=$? $(tail -1 chiprun_out/sets/${W}_set${set}_${s}.out | cut -c1-330)"
  done
done
for i in 7 8; do
  s=$((B+i))
  python3 benchmark/run.py --workload $W --seed $s --seconds 51 --trace 1 > chiprun_out/sets/${W}_trace_${s}.out 2> chiprun_out/sets/${W}_trace_${s}.err
  echo "$W trace seed $s rc=$? $(tail -1 chiprun_out/sets/${W}_trace_${s}.out | cut -c1-200)"
done
