#!/bin/bash
# PR 33, chip call 1: the fixture, one traced run, one plain run.
set +e
OUT=chiprun_out/pr33
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.veles_cache/pr33_xla
unset JAX_COMPILATION_CACHE_MAX_SIZE
CELL=keye-vl2-ep8share.pretrain8k-1seq
date
python3 benchmark/tests/record_indexed_lm.py $OUT/tiny-indexed.v5e-1.xplane.pb > $OUT/record.log 2>&1
echo "record rc=$?"; tail -n 2 $OUT/record.log | cut -c1-600
date
python3 benchmark/run.py --workload $CELL --seed 2147483777 --seconds 20 --trace 1 > $OUT/trace_2147483777.log 2>&1
echo "trace rc=$?"; grep -v "^W0\|^I0" $OUT/trace_2147483777.log | tail -n 60 | cut -c1-1500
date
python3 benchmark/run.py --workload $CELL --seed 3000000033 --seconds 20 --trace 0 > $OUT/run_3000000033.log 2>&1
echo "run rc=$?"; grep "agreement\|checks\|set-up\|window" $OUT/run_3000000033.log | cut -c1-1500; tail -n 1 $OUT/run_3000000033.log
date
