#!/bin/bash
# PR 35, call 2 (one chip): keye-vl2-ep8share.pretrain8k-1seq. The
# change cold (a cache directory of its own) and then warm, both
# traced (the set-up table of each); then the off cost in a token
# cell: the four end-to-end metrics of the parent and of the change,
# untraced, on one seed. A run is skipped when the call's time runs
# short. A record of the call as it ran.
set +e
TOP=$PWD
T0=$(date +%s)
OUT=$PWD/chiprun_out/pr35b
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.veles_cache/pr35_xla
unset JAX_COMPILATION_CACHE_MAX_SIZE
CELL=keye-vl2-ep8share.pretrain8k-1seq
run() {  # name side seed trace latest-start
  NAME=$1; SIDE=$2; SEED=$3; TRACE=$4
  if [ $(( $(date +%s) - T0 )) -gt $5 ]; then echo "skipped $NAME: $(( $(date +%s) - T0 )) s gone"; return; fi
  if [ $SIDE = parent ]; then cd $TOP/.checkouts/parent; else cd $TOP; fi
  timeout -k 10 1500 python3 benchmark/run.py --workload $CELL --seed $SEED --seconds 20 --trace $TRACE > $OUT/$NAME.log 2>$OUT/$NAME.err
  echo "== $NAME: $SIDE seed $SEED trace $TRACE rc=$? at $(( $(date +%s) - T0 )) s"
  grep "^set-up:\|^window:\|^cell:" $OUT/$NAME.log | cut -c1-200
  tail -n 1 $OUT/$NAME.log | grep -o '"metrics".*' | cut -c1-2600
  if [ $TRACE = 1 ]; then
    grep "^head \|first steady epoch" $OUT/$NAME.log | cut -c1-300
  fi
  cd $TOP
}
run cold change 2147484001 1 0
run warm change 2147484002 1 1700
run off_p parent 2147484003 0 2200
run off_c change 2147484003 0 2750
echo "-- the warm table"
sed -n '/^set-up by the program/,/^head /p' $OUT/warm.log | cut -c1-150 | grep -v "^ .* 0\.0[0-4][0-9]  " | head -n 120
echo "done at $(( $(date +%s) - T0 )) s"
