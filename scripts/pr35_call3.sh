#!/bin/bash
# PR 35, call 3 (four chips): alexnet227-dp4.resident. One untraced
# run that fills the cache, then the warm run, traced: the set-up
# table with dataset_shard in it. A record of the call as it ran.
set +e
T0=$(date +%s)
OUT=$PWD/chiprun_out/pr35c
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.veles_cache/pr35_xla
unset JAX_COMPILATION_CACHE_MAX_SIZE
CELL=alexnet227-dp4.resident
run() {  # name seed trace
  timeout -k 10 600 python3 benchmark/run.py --workload $CELL --seed $2 --seconds 20 --trace $3 > $OUT/$1.log 2>$OUT/$1.err
  echo "== $1: seed $2 trace $3 rc=$? at $(( $(date +%s) - T0 )) s"
  grep "^set-up:\|^window:\|^cell:" $OUT/$1.log | cut -c1-200
  tail -n 1 $OUT/$1.log | grep -o '"metrics".*' | cut -c1-2200
}
run cold 2147484101 1
run warm 2147484102 1
echo "-- the cold table's last line"
grep "^head " $OUT/cold.log | cut -c1-300
echo "-- the warm table"
sed -n '/^set-up by the program/,/^head /p' $OUT/warm.log | cut -c1-150 | head -n 100
echo "done at $(( $(date +%s) - T0 )) s"
