#!/bin/bash
# usage: sets.sh <workload> <seconds> <out> <seeds...>  -> runs the seeds twice (set A, set B)
w=$1; s=$2; out=$3; shift 3
for set in A B; do for seed in "$@"; do
  python3 benchmark/run.py --workload $w --seed $seed --seconds $s --trace 0 2>/dev/null | tail -8 > chiprun_out/_last.txt
  echo "SET $set seed $seed rc=$? $(tail -1 chiprun_out/_last.txt)" >> $out
  grep -h '"compared"\|"detail"' chiprun_out/_last.txt | cut -c1-1800 >> $out.detail
done; done
