#!/bin/bash
# PR 38, chip call 1 (one chip): alexnet227.resident, parent
# (.checkouts/parent, `git archive` of 809d2aa) and the working tree,
# each cold once into a compile cache of its own and then warm twice,
# every run traced so that the set-up table prints. Order: parent,
# change, change, parent, parent, change. A record of the call as it ran.
set +e
TOP=$PWD
T0=$(date +%s)
OUT=$TOP/chiprun_out/pr38a
mkdir -p $OUT
unset JAX_COMPILATION_CACHE_MAX_SIZE
CELL=alexnet227.resident
ls $TOP/.checkouts/parent/benchmark/run.py || exit 2
run() {  # side name seed
  if [ $1 = parent ]; then cd $TOP/.checkouts/parent; else cd $TOP; fi
  export JAX_COMPILATION_CACHE_DIR=$TOP/.veles_cache/pr38_xla_$1
  timeout -k 10 900 python3 benchmark/run.py --workload $CELL --seed $3 --seconds 20 --trace 1 > $OUT/$1_$2.log 2>$OUT/$1_$2.err
  echo "== $1 $2: seed $3 rc=$? at $(( $(date +%s) - T0 )) s"
  tail -n 1 $OUT/$1_$2.log | grep -o '"correct".*' | sed 's/"breakdown".*"metrics"/"metrics"/' | cut -c1-2600
  grep "^head " $OUT/$1_$2.log | cut -c1-300
  sed -n '/^by program, s:/,/more programs under/p' $OUT/$1_$2.log | cut -c1-160 | head -n 12
  cd $TOP
}
run parent cold 2147485101
run change cold 2147485101
run change warm1 2147485102
run parent warm1 2147485102
run parent warm2 2147485103
run change warm2 2147485103
echo "-- the change's warm table"
sed -n '/^set-up by the program/,/^head /p' $OUT/change_warm1.log | cut -c1-150 | head -n 80
echo "done at $(( $(date +%s) - T0 )) s"
