#!/bin/bash
# PR 38, chip call 3 (FOUR chips): alexnet227-dp4.resident, the cell
# whose held executables are partitioned programs over a planned
# fetch (`Compiled` over `NamedSharding`s: ran on host devices only
# before this call). Parent (.checkouts/parent) and the working tree,
# each cold once into a compile cache of its own, traced (the set-up
# table), then warm once, untraced (the end-to-end metrics). Order:
# parent, change, change, parent. A record of the call as it ran.
set +e
TOP=$PWD
T0=$(date +%s)
OUT=$TOP/chiprun_out/pr38c
mkdir -p $OUT
unset JAX_COMPILATION_CACHE_MAX_SIZE
CELL=alexnet227-dp4.resident
ls $TOP/.checkouts/parent/benchmark/run.py || exit 2
run() {  # side name seed trace
  if [ $1 = parent ]; then cd $TOP/.checkouts/parent; else cd $TOP; fi
  export JAX_COMPILATION_CACHE_DIR=$TOP/.veles_cache/pr38_xla_$1
  timeout -k 10 700 python3 benchmark/run.py --workload $CELL --seed $3 --seconds 20 --trace $4 > $OUT/$1_$2.log 2>$OUT/$1_$2.err
  echo "== $1 $2: seed $3 trace $4 rc=$? at $(( $(date +%s) - T0 )) s"
  grep "^set-up:\|^window:" $OUT/$1_$2.log | cut -c1-200
  tail -n 1 $OUT/$1_$2.log | grep -o '"correct".*' | sed 's/"breakdown".*"metrics"/"metrics"/' | cut -c1-2900
  grep "^head " $OUT/$1_$2.log | cut -c1-300
  sed -n '/^by program, s:/,/more programs under/p' $OUT/$1_$2.log | cut -c1-160 | head -n 6
  cd $TOP
}
run parent cold 2147485301 1
run change cold 2147485301 1
run change warm 2147485302 0
run parent warm 2147485302 0
echo "-- the change's cold table"
sed -n '/^set-up by the program/,/^head /p' $OUT/change_cold.log | cut -c1-150 | grep -v "^ .* 0\.0[0-9][0-9]  " | head -n 60
echo "done at $(( $(date +%s) - T0 )) s"
