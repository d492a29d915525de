#!/usr/bin/env bash
# Measure one cell the way its bounds and limits are set, on the chip
# this runs on, from the root of a checkout:
#
#   chipbench/tools/prove.sh <workload> <out_dir> <seed_base> \
#       [sweep_rates] [readings_seeds] [runs_per_set] [traced_runs]
#
# 1. with sweep_rates (e.g. 2,3,4,5): a knee sweep of run_seconds
#    windows on seeds base+900 and base+901, and the cell's traffic file
#    set to four fifths of the knee (knee.py);
# 2. the comparison's readings (readings.py) on readings_seeds seeds
#    (default 12; 0 skips) from base+1, the control on the first three,
#    10 s windows at the cell's rate;
# 3. two sets of runs_per_set runs (default 6) at run_seconds on the
#    same seeds, base+101 onwards;
# 4. traced_runs (default 3) traced runs on seeds base+201 onwards.
# Everything goes under <out_dir>; a short digest is printed at the end.
set -u
cell=$1 out=$2 base=$3 rates=${4:-} nread=${5:-12} nper=${6:-6} ntr=${7:-3}
field() {
  python3 -c "import json; d = json.load(open('BENCHMARK.json')); $1"
}
secs=$(field "print(d['run_seconds'])")
mix=$(field "print([w['traffic'] for w in d['workloads'] if w['name'] == '$cell'][0])")
mkdir -p "$out"
if [ -n "$rates" ]; then
  python3 chipbench/tools/sweep.py --workload "$cell" \
    --seeds "$((base + 900)),$((base + 901))" --seconds "$secs" \
    --rates "$rates" > "$out/sweep.jsonl" 2> "$out/sweep.err"
  python3 chipbench/tools/knee.py "$out/sweep.jsonl" \
    --set "chipbench/traffic/$mix.json" > "$out/knee.json" || exit 1
fi
if [ "$nread" -gt 0 ]; then
  python3 chipbench/tools/readings.py --workload "$cell" --seconds 10 \
    --seeds "$(seq -s, $((base + 1)) $((base + nread)))" \
    --control-seeds "$((base + 1)),$((base + 2)),$((base + 3))" \
    > "$out/readings.jsonl" 2> "$out/readings.err"
fi
full=() traced=()
for i in $(seq 1 "$nper"); do full+=("$cell:$((base + 100 + i)):$secs:0"); done
for i in $(seq 1 "$ntr"); do traced+=("$cell:$((base + 200 + i)):$secs:1"); done
for set in setA setB; do
  [ "$nper" -gt 0 ] && python3 chipbench/tools/series.py --out "$out/$set" \
    "${full[@]}" > /dev/null
done
[ "$ntr" -gt 0 ] && python3 chipbench/tools/series.py --out "$out/traced" \
  "${traced[@]}" > /dev/null
python3 chipbench/tools/digest.py "$out"
