#!/bin/bash
# PR 34, call 5 (after the review; four chips, 40 chip-minutes in all):
# the parent, then two placements of the fences on one seed, both
# traced: "change", the committed files as they are (.checkouts/change
# is `git archive $(git write-tree)`: a fence behind the entry unit,
# u12's gradient due behind u06 and u10's behind u00, call 3's control
# without its compiler options), and "alt", through
# scripts/pr34_fences.py: the fence behind the entry unit on the
# cotangent alone and u03's kernel through a fence in front of u03, no
# dense gradient held (the review's placement; no temporaries over the
# parent's). Then whichever of the two read more samples/s on a second
# seed, and the parent on it. A run is skipped when the call's time
# runs short. (As it ran, for the record: `rate` read 0 for both traced
# runs, whose last line carries no samples/s, so "alt" went on by
# default; the window lines give 25,894.6 and 25,433.6. The final tree
# ships "alt" less the fence in front of u03: PERF.md section 6.)
set +e
TOP=$PWD
T0=$(date +%s)
OUT=$PWD/chiprun_out/pr34e
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.veles_cache/pr34_xla}
CELL=alexnet227-dp4.resident
ALT="0:;2:3"
cp scripts/step_timeline.py .checkouts/parent/scripts/
rate() { tail -n 1 $OUT/run_$1.log | python3 -c "import json,sys; print(json.loads(sys.stdin.read())['metrics']['train_samples_per_s']['value'])" 2>/dev/null || echo 0; }
run() {  # side seed trace
  SIDE=$1; SEED=$2; TRACE=$3
  if [ $(( $(date +%s) - T0 )) -gt ${4:-999} ]; then echo "skipped $SIDE $SEED: $(( $(date +%s) - T0 )) s gone"; return; fi
  DIR=$SIDE; CMD="benchmark/run.py"
  if [ $SIDE = alt ]; then DIR=change; CMD="scripts/pr34_fences.py $ALT"; fi
  cd $TOP/.checkouts/$DIR
  timeout -k 10 300 python3 $CMD --workload $CELL --seed $SEED --seconds 20 --trace $TRACE > $OUT/run_${SIDE}_$SEED.log 2>&1
  echo "run $SIDE seed $SEED trace $TRACE rc=$? at $(( $(date +%s) - T0 )) s"
  grep "set-up\|window:" $OUT/run_${SIDE}_$SEED.log | cut -c1-220
  tail -n 1 $OUT/run_${SIDE}_$SEED.log | grep -o '"metrics".*' | cut -c1-2300
  if [ $TRACE = 1 ]; then
    grep -A 22 "^  scope " $OUT/run_${SIDE}_$SEED.log | head -n 24 | cut -c1-110
    python3 scripts/step_timeline.py $CELL $OUT/timeline_$SIDE.txt 2>&1 | tail -n 1
  fi
  cd $TOP
}
S1=34000505; S2=2147485001
run parent $S1 0
run change $S1 1
run alt $S1 1
A=$(rate change_$S1); B=$(rate alt_$S1)
WIN=$(python3 -c "print('alt' if $B >= $A else 'change')")
echo "change $A alt $B -> $WIN on seed $S2"
run $WIN $S2 0 430
run parent $S2 0 515
echo "done at $(( $(date +%s) - T0 )) s"
