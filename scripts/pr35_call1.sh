#!/bin/bash
# PR 35, call 1 (one chip): alexnet227.resident. The change cold and
# then warm, both traced (the set-up table of each); the parent with
# this PR's benchmark files laid over it, traced (it must print its
# old metrics, none of the five, and exit 0); then the off cost: the
# four end-to-end metrics of parent, change, change, parent untraced,
# two seeds. .checkouts/parent is `git archive` of the parent commit
# with BENCHMARK.json and benchmark/ copied over it. A record of the
# call as it ran; it runs against trees of its own.
set +e
TOP=$PWD
T0=$(date +%s)
OUT=$PWD/chiprun_out/pr35a
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.veles_cache/pr35_xla
unset JAX_COMPILATION_CACHE_MAX_SIZE
CELL=alexnet227.resident
run() {  # name side seed trace
  NAME=$1; SIDE=$2; SEED=$3; TRACE=$4
  if [ $SIDE = parent ]; then cd $TOP/.checkouts/parent; else cd $TOP; fi
  timeout -k 10 600 python3 benchmark/run.py --workload $CELL --seed $SEED --seconds 20 --trace $TRACE > $OUT/$NAME.log 2>$OUT/$NAME.err
  echo "== $NAME: $SIDE seed $SEED trace $TRACE rc=$? at $(( $(date +%s) - T0 )) s"
  grep "^set-up:\|^window:\|^cell:" $OUT/$NAME.log | cut -c1-200
  tail -n 1 $OUT/$NAME.log | grep -o '"metrics".*' | cut -c1-1800
  if [ $TRACE = 1 ]; then
    grep "^head \|first steady epoch" $OUT/$NAME.log | cut -c1-300
  fi
  cd $TOP
}
run cold change 2147483901 1
run warm change 2147483902 1
run parent_overlay parent 2147483903 1
run off_p1 parent 2147483904 0
run off_c1 change 2147483904 0
run off_c2 change 2147483905 0
run off_p2 parent 2147483905 0
echo "-- the warm table"
sed -n '/^set-up by the program/,/^head /p' $OUT/warm.log | cut -c1-150 | head -n 90
echo "done at $(( $(date +%s) - T0 )) s"
