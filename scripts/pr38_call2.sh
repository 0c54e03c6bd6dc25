#!/bin/bash
# PR 38, chip call 2 (one chip): the claimed cell,
# keye-vl2-ep8share.pretrain8k-1seq, parent (.checkouts/parent, `git
# archive` of 809d2aa) and the working tree, each cold once into a
# compile cache of its own and then warm once, every run traced so
# that the set-up table prints. Order: parent, change, change, parent
# (a third run a side does not fit a call's 3,600 s: a cold parent is
# 19 minutes). A record of the call as it ran.
set +e
TOP=$PWD
T0=$(date +%s)
OUT=$TOP/chiprun_out/pr38b
mkdir -p $OUT
unset JAX_COMPILATION_CACHE_MAX_SIZE
CELL=keye-vl2-ep8share.pretrain8k-1seq
ls $TOP/.checkouts/parent/benchmark/run.py || exit 2
run() {  # side name seed
  if [ $1 = parent ]; then cd $TOP/.checkouts/parent; else cd $TOP; fi
  export JAX_COMPILATION_CACHE_DIR=$TOP/.veles_cache/pr38_xla_$1
  timeout -k 10 1500 python3 benchmark/run.py --workload $CELL --seed $3 --seconds 20 --trace 1 > $OUT/$1_$2.log 2>$OUT/$1_$2.err
  echo "== $1 $2: seed $3 rc=$? at $(( $(date +%s) - T0 )) s"
  tail -n 1 $OUT/$1_$2.log | grep -o '"correct".*' | sed 's/"breakdown".*"metrics"/"metrics"/' | cut -c1-3800
  grep "^head " $OUT/$1_$2.log | cut -c1-300
  sed -n '/^by program, s:/,/more programs under/p' $OUT/$1_$2.log | cut -c1-160 | head -n 14
  cd $TOP
}
run parent cold 2147485201
run change cold 2147485201
run change warm 2147485202
run parent warm 2147485202
echo "-- the change's warm table"
sed -n '/^set-up by the program/,/^head /p' $OUT/change_warm.log | cut -c1-150 | head -n 90
echo "done at $(( $(date +%s) - T0 )) s"
