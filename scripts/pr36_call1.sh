#!/bin/bash
# PR 36, call 1 (four chips): the change traced, the parent traced
# (scripts/step_timeline.py on each: the all-to-all's time and
# veles.in's), then the change and the parent untraced on a second
# seed. The change is the working tree at the root; the parent is
# .checkouts/parent (`git archive` of fbcff54). A run is skipped when
# the call's time runs short. A record of the call as it ran.
set +e
TOP=$PWD
T0=$(date +%s)
OUT=$PWD/chiprun_out/pr36a
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.veles_cache/pr36_xla}
CELL=alexnet227-dp4.resident
cp scripts/step_timeline.py .checkouts/parent/scripts/
run() {  # side dir seed trace latest-start
  if [ $(( $(date +%s) - T0 )) -gt ${5:-9999} ]; then echo "skipped $1 $3: $(( $(date +%s) - T0 )) s gone"; return; fi
  cd $2
  timeout -k 10 420 python3 benchmark/run.py --workload $CELL --seed $3 --seconds 20 --trace $4 > $OUT/run_$1_$3.log 2>&1
  echo "== run $1 seed $3 trace $4 rc=$? at $(( $(date +%s) - T0 )) s"
  grep "set-up\|window:" $OUT/run_$1_$3.log | cut -c1-220
  tail -n 1 $OUT/run_$1_$3.log | grep -o '"metrics".*' | cut -c1-2600
  if [ $4 = 1 ]; then
    grep -A 22 "^  scope " $OUT/run_$1_$3.log | head -n 24 | cut -c1-110
    python3 scripts/step_timeline.py $CELL $OUT/timeline_$1.txt 2>&1 | tail -n 1
  fi
  cd $TOP
}
S1=36000101; S2=2147483901
run change $TOP $S1 1
run parent $TOP/.checkouts/parent $S1 1 400
run change $TOP $S2 0 620
run parent $TOP/.checkouts/parent $S2 0 720
echo "done at $(( $(date +%s) - T0 )) s"
