#!/bin/bash
# Two sets of runs of one cell, as the driver measures a new cell: every run
# its own seed, the runs of one cell in one call. The result lines go to
# chiprun_out/benchmark/measure/<cell>.jsonl; benchmark/rehearse/spread.py
# reads them.
#   chiprun --chips 1 -- bash benchmark/rehearse/measure.sh <cell> [runs_per_set] [first_seed] [traced_runs]
set -u
cell=$1; runs=${2:-6}; seed=${3:-100}; traced=${4:-1}
seconds=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
out=chiprun_out/benchmark/measure; mkdir -p $out
: > $out/$cell.jsonl
for set in 1 2; do
  for i in $(seq 1 $runs); do
    seed=$((seed + 1))
    python3 benchmark/run.py --workload $cell --seed $seed --seconds $seconds --trace 0 \
      > $out/$cell.last.log 2>&1
    rc=$?
    line=$(tail -n 1 $out/$cell.last.log)
    echo "{\"set\": $set, \"seed\": $seed, \"rc\": $rc, \"line\": $line}" >> $out/$cell.jsonl
    grep "window:" $out/$cell.last.log | tail -n 1
    echo "set $set run $i seed $seed rc $rc: $line" | cut -c1-420
  done
done
for i in $(seq 1 $traced); do
  seed=$((seed + 1))
  python3 benchmark/run.py --workload $cell --seed $seed --seconds $seconds --trace 1 \
    > $out/$cell.traced.log 2>&1
  echo "traced seed $seed rc $?: $(tail -n 1 $out/$cell.traced.log)"
  echo "{\"set\": 0, \"seed\": $seed, \"line\": $(tail -n 1 $out/$cell.traced.log)}" >> $out/$cell.jsonl
done
