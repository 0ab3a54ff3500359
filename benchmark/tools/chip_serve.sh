#!/bin/sh
# the serving mix on the chip in one call: the knee sweep, 0.8 x knee written
# into the traffic file of THIS copy, then runs of the proposed cell
# usage: chip_serve.sh <seconds> <runs>
s=$1; n=${2:-3}
w=bert-base.serve-embed-open; B=benchmark/proposed/serve-embed-open.json
mkdir -p chiprun_out/$w
python3 benchmark/tools/sweep_knee.py --config bert-base --traffic embed-open-r80 \
  --first-rate 80 --steps 11 --seconds 8 > chiprun_out/$w/sweep.out 2> chiprun_out/$w/sweep.err
echo "rc=$? sweep"; grep rate_per_s chiprun_out/$w/sweep.out | cut -c1-600; tail -n 1 chiprun_out/$w/sweep.out
python3 - <<'PY'
import json
knee = json.loads(open("chiprun_out/bert-base.serve-embed-open/sweep.out").read().strip().splitlines()[-1])["knee_per_s"]
p = "benchmark/traffic/embed-open-r80.json"; t = json.load(open(p))
if knee:
    t["rate_per_s"] = round(0.8 * knee, 1)
json.dump(t, open(p, "w"), indent=2)
print("rate_per_s", t["rate_per_s"])
PY
i=0
while [ $i -lt $n ]; do
  i=$((i + 1))
  python3 benchmark/run.py --append $B --workload $w --seed $((200 + i)) --seconds $s --trace 0 \
    > chiprun_out/$w/run$i.out 2> chiprun_out/$w/run$i.err
  echo "rc=$? run$i"; tail -n 1 chiprun_out/$w/run$i.out
done
python3 benchmark/run.py --append $B --workload $w --seed 299 --seconds $s --trace 1 \
  > chiprun_out/$w/trace.out 2> chiprun_out/$w/trace.err
echo "rc=$? trace"; tail -n 1 chiprun_out/$w/trace.out | cut -c1-3000
grep -h "memory_stats\|serve_check" chiprun_out/$w/run1.out | cut -c1-1500
tail -n 5 chiprun_out/$w/sweep.err chiprun_out/$w/run1.err | cut -c1-600
