#!/bin/bash
# PR 34, final four-chip call: parent and the committed files of the
# change (.checkouts/final is `git archive $(git write-tree)`), P C C P
# on two seeds, the first pair traced; then the change with its ties
# kept and its compiler options emptied, traced.
# (As it ran, for the record: .checkouts/noopts.py was benchmark/run.py with
# dp.ASYNC_GRADIENT_OPTIONS emptied, a name this PR's final tree no longer has;
# calls 1 and 2 ran scratch trees that are in no commit, and their scripts were
# not kept: their variants and readings are in PERF.md section 6.)
set +e
TOP=$PWD
OUT=$PWD/chiprun_out/pr34c
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.veles_cache/pr34_xla}
CELL=alexnet227-dp4.resident
date
for RUN in parent:34000303:1 final:34000303:1 final:2147484034:0 parent:2147484034:0 noopts:34000303:1; do
  IFS=: read SIDE SEED TRACE <<< "$RUN"
  DIR=$SIDE; CMD="benchmark/run.py"
  if [ $SIDE = noopts ]; then DIR=final; CMD="$TOP/.checkouts/noopts.py"; fi
  cd $TOP/.checkouts/$DIR
  timeout -k 10 400 python3 $CMD --workload $CELL --seed $SEED --seconds 20 --trace $TRACE > $OUT/run_${SIDE}_$SEED.log 2>&1
  echo "run $SIDE seed $SEED trace $TRACE rc=$?"
  grep "set-up\|window:" $OUT/run_${SIDE}_$SEED.log | cut -c1-220
  tail -n 1 $OUT/run_${SIDE}_$SEED.log | grep -o '"metrics".*' | cut -c1-2300
  if [ $TRACE = 1 ] && [ $SIDE != parent ]; then python3 scripts/step_timeline.py $CELL $OUT/timeline_$SIDE.txt 2>&1 | tail -2; fi
  cd $TOP; date
done
