#!/bin/bash
# PR 33, chip call 3: the Laguna cell, which shares GroupedAttentionForward,
# on the change and on the parent, one seed.
set +e
OUT=$PWD/chiprun_out/pr33
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.veles_cache/pr33_xla
unset JAX_COMPILATION_CACHE_MAX_SIZE
CELL=laguna-s21-ep32share.pretrain-1seq
date
python3 benchmark/run.py --workload $CELL --seed 424243 --seconds 20 --trace 0 > $OUT/change_laguna.log 2>&1
echo "change laguna rc=$?"; grep "agreement\|set-up" $OUT/change_laguna.log | cut -c1-700; tail -n 1 $OUT/change_laguna.log
date
(cd .checkouts/parent; python3 benchmark/run.py --workload $CELL --seed 424243 --seconds 20 --trace 0 > $OUT/parent_laguna.log 2>&1; echo "parent laguna rc=$?"; grep "agreement\|set-up" $OUT/parent_laguna.log | cut -c1-700; tail -n 1 $OUT/parent_laguna.log)
date
python3 benchmark/run.py --workload $CELL --seed 424243 --seconds 20 --trace 0 > $OUT/change_laguna2.log 2>&1
echo "change laguna again rc=$?"; tail -n 1 $OUT/change_laguna2.log
date
