#!/bin/sh
# two sets of N runs of one cell in one call, every run another seed, then a
# traced run; last lines go to chiprun_out/<cell>/sets.jsonl
# usage: chip_sets.sh <workload> <seconds> [runs-per-set=6] [first-seed=100]
w=$1; s=$2; n=${3:-6}; seed=${4:-100}
mkdir -p chiprun_out/$w
: > chiprun_out/$w/sets.jsonl
for set in 1 2; do
  i=0
  while [ $i -lt $n ]; do
    seed=$((seed + 1)); i=$((i + 1))
    python3 benchmark/run.py --workload $w --seed $seed --seconds $s --trace 0 \
      > chiprun_out/$w/set$set.$i.out 2> chiprun_out/$w/set$set.$i.err
    rc=$?
    echo "{\"set\": $set, \"seed\": $seed, \"rc\": $rc, \"line\": $(tail -n 1 chiprun_out/$w/set$set.$i.out)}" \
      >> chiprun_out/$w/sets.jsonl
  done
done
python3 benchmark/run.py --workload $w --seed $((seed + 1)) --seconds $s --trace 1 \
  > chiprun_out/$w/sets_trace.out 2> chiprun_out/$w/sets_trace.err
echo "{\"set\": \"trace\", \"rc\": $?, \"line\": $(tail -n 1 chiprun_out/$w/sets_trace.out)}" >> chiprun_out/$w/sets.jsonl
cp .benchmark_out/$w/trace_excerpt.json chiprun_out/$w/ 2>/dev/null
grep -h "setup_timeline" chiprun_out/$w/set1.1.out chiprun_out/$w/set1.2.out | cut -c1-900
python3 benchmark/tools/spread.py chiprun_out/$w/sets.jsonl
