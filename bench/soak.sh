#!/bin/bash
# Back-to-back runs of one cell on one machine, as the driver makes them:
# each a new process, each printing its exit code and its result line.
#   bash bench/soak.sh <cell> <seconds> <out_dir> <trace> <seed>...
# Where the code does not change afterwards these are the proof runs too.
cell=$1; seconds=$2; out=$3; trace=$4; shift 4
mkdir -p "$out"
for seed in "$@"; do
  n=$(ls "$out" | grep -c "^$cell\.t$trace\..*\.log$")
  log="$out/$cell.t$trace.$n.seed$seed.log"
  t0=$(date +%s)
  python3 bench/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace "$trace" > "$log" 2>&1
  rc=$?
  left=$(ps -eo pid,args | grep -c "ray_tpu._privat[e]")
  echo "SOAK $cell trace=$trace run=$n seed=$seed exit=$rc wall=$(( $(date +%s) - t0 ))s processes_left=$left $(grep -o 'setup_s [0-9.]*' "$log" | tail -1)"
  echo "SOAKLINE $(tail -n 1 "$log" | cut -c1-1200)"
done
