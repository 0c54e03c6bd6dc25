#!/bin/bash
# PR 36, call 4 (four chips), on the final tree: .checkouts/final is
# `git archive $(git write-tree)` (the committed files are enough),
# .checkouts/parent `git archive fbcff54`. Untraced pairs final /
# parent on seeds of their own (P C C P order over the call), one
# traced run of the final tree with scripts/step_timeline.py, then a
# third pair if the call's time allows. A run is skipped when the
# time runs short. A record of the call as it ran.
set +e
TOP=$PWD
T0=$(date +%s)
OUT=$PWD/chiprun_out/pr36d
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.veles_cache/pr36_xla}
CELL=alexnet227-dp4.resident
run() {  # side seed trace latest-start
  if [ $(( $(date +%s) - T0 )) -gt ${4:-9999} ]; then echo "skipped $1 $2: $(( $(date +%s) - T0 )) s gone"; return; fi
  cd $TOP/.checkouts/$1
  timeout -k 10 420 python3 benchmark/run.py --workload $CELL --seed $2 --seconds 20 --trace $3 > $OUT/run_$1_$2.log 2>&1
  echo "== run $1 seed $2 trace $3 rc=$? at $(( $(date +%s) - T0 )) s"
  grep "set-up\|window:\|build: gspmd" $OUT/run_$1_$2.log | cut -c1-220
  tail -n 1 $OUT/run_$1_$2.log | grep -o '"metrics".*' | cut -c1-2600
  if [ $3 = 1 ]; then
    grep -A 22 "^  scope " $OUT/run_$1_$2.log | head -n 24 | cut -c1-110
    grep "dataset_shard\|dataset_stage" $OUT/run_$1_$2.log | cut -c1-120
    python3 scripts/step_timeline.py $CELL $OUT/timeline_$1.txt 2>&1 | tail -n 1
  fi
  cd $TOP
}
S=2147483921
run parent $S 0
run final $S 0
run final $((S+1)) 0 500
run parent $((S+1)) 0 600
run final $((S+2)) 1 700
run parent $((S+3)) 0 900
run final $((S+3)) 0 1000
echo "done at $(( $(date +%s) - T0 )) s"
