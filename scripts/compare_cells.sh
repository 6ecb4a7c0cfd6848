#!/bin/bash
# Parent against change on benchmark cells, on the machine with the chip.
#
#   scripts/compare_cells.sh PARENT_DIR OUT_DIR WORKLOAD SEED [SEED...]
#
# PARENT_DIR is a checkout of the parent commit (for instance
# `git archive <commit> | tar -x -C <dir>`, in a directory .gitignore
# lists); the change is the checkout this script lies in.  For each seed,
# bench/run.py runs on both sides with that seed, in the order parent,
# change for the odd pairs and change, parent for the even ones, with the
# cell's run_seconds from BENCHMARK.json.  Then each side makes one traced
# run (--trace 1) whose profile is kept (the sides in TRACE_SIDES, default
# "change parent"), and `python3 -m bench.scopes` reads each traced job's
# device seconds by named scope.  Result lines go
# to OUT_DIR/WORKLOAD.jsonl as "<side> <seed> <line>", the scopes to
# OUT_DIR/WORKLOAD.<side>.scopes.txt and each run's stderr to OUT_DIR.
set -u
parent=$(cd "$1" && pwd); out=$2; workload=$3; shift 3
change=$(cd "$(dirname "$0")/.." && pwd)
mkdir -p "$out"; out=$(cd "$out" && pwd)
secs=$(python3 -c "import json; print(json.load(open('$change/BENCHMARK.json'))['run_seconds'])")

one() {  # side dir seed trace
  local tag="$1.$3.t$4"
  local extra=()
  [ "$4" = 1 ] && extra=(--trace-dir "$out/$workload.$1.prof")
  (cd "$2" && python3 bench/run.py --workload "$workload" --seed "$3" \
      --seconds "$secs" --trace "$4" "${extra[@]}" \
      > "$out/$workload.$tag.out" 2> "$out/$workload.$tag.err")
  local rc=$?
  echo "$1 $3 $(tail -n 1 "$out/$workload.$tag.out")" >> "$out/$workload.jsonl"
  echo "$workload $tag rc=$rc" >&2
}

i=0
for seed in "$@"; do
  if [ $((i % 2)) = 0 ]; then
    one parent "$parent" "$seed" 0; one change "$change" "$seed" 0
  else
    one change "$change" "$seed" 0; one parent "$parent" "$seed" 0
  fi
  i=$((i + 1))
done
seed=$(( $1 + 7 ))
for side in ${TRACE_SIDES:-change parent}; do
  dir=$parent; [ $side = change ] && dir=$change
  one $side "$dir" "$seed" 1
  (cd "$change" && python3 -m bench.scopes "$out/$workload.$side.prof") \
      > "$out/$workload.$side.scopes.txt" 2>&1
done
