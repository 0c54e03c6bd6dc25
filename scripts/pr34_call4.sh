#!/bin/bash
# PR 34, one chip: the one-chip AlexNet cell, the change's committed
# (As it ran, for the record.)
# files then the parent, one seed.
set +e
TOP=$PWD
OUT=$PWD/chiprun_out/pr34d
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.veles_cache/pr34_xla}
CELL=alexnet227.resident
for SIDE in final parent; do
  cd $TOP/.checkouts/$SIDE
  timeout -k 10 400 python3 benchmark/run.py --workload $CELL --seed 34000404 --seconds 20 --trace 0 > $OUT/run_$SIDE.log 2>&1
  echo "run $SIDE rc=$?"
  grep "set-up\|window:" $OUT/run_$SIDE.log | cut -c1-220
  tail -n 1 $OUT/run_$SIDE.log | cut -c1-800
  cd $TOP; date
done
