#!/bin/bash
# Everything a round cell needs on the chip, in one process chain on one
# machine: a cold run, readings of the program, control and faults on three
# seeds, two sets of six runs on the same six seeds, and three traced runs.
#
#     bash chipbench/prove.sh <cell> <out-dir> [run-seconds]
#
# Each run's standard output and error go to <out-dir>; one summary line per
# run, and per reading, goes to standard output.
W=$1; O=$2; SEC=${3:-30}
mkdir -p "$O"
run() {
  local s=$1 t=$2 k=$3 sec=$4
  local f="$O/$k.$s.$t"
  timeout 1300 python3 chipbench/run.py --workload "$W" --seed "$s" --seconds "$sec" --trace "$t" > "$f.out" 2> "$f.err"
  local rc=$?
  echo "== $(date +%T) set=$k seed=$s trace=$t rc=$rc"
  [ $rc = 0 ] || tail -8 "$f.err" | cut -c1-1000
  python3 chipbench/summary.py "$f.out"
  return $rc
}
run 4000000011 0 cold 10 || { echo "the cold run failed"; exit 1; }
timeout 1200 python3 chipbench/readings.py --workload "$W" --seeds 4000000021 4000000022 4000000023 \
  --out "$O/readings.jsonl" > "$O/readings.out" 2> "$O/readings.err"
echo "== $(date +%T) readings rc=$?"
tail -4 "$O/readings.err" | cut -c1-1000
[ -f "$O/readings.jsonl" ] && python3 chipbench/summary.py "$O/readings.jsonl"
for k in A B; do
  for s in 4000000041 4000000042 4000000043 4000000044 4000000045 4000000046; do run $s 0 $k "$SEC"; done
done
for s in 4000000051 4000000052 4000000053; do run $s 1 T "$SEC"; done
