#!/bin/bash
# PR 33, chip call 4: the committed files alone (.checkouts/final is
# `git archive $(git write-tree)`): a fresh seed cold, two seeds again
# (is a run's speed the seed's?), one traced run.
set +e
OUT=$PWD/chiprun_out/pr33
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.veles_cache/pr33_xla
unset JAX_COMPILATION_CACHE_MAX_SIZE
CELL=keye-vl2-ep8share.pretrain8k-1seq
cd .checkouts/final
date
for SEED in 2147484111 3000000033 777000111 3000000033; do
  python3 benchmark/run.py --workload $CELL --seed $SEED --seconds 20 --trace 0 > $OUT/final_$SEED.$(date +%s).log 2>&1
  echo "final $SEED rc=$?"; L=$(ls -t $OUT/final_$SEED.*.log | head -n 1); grep "agreement\|set-up\|window:" $L | cut -c1-900; tail -n 1 $L
  date
done
python3 benchmark/run.py --workload $CELL --seed 424242 --seconds 20 --trace 1 > $OUT/final_trace_424242.log 2>&1
echo "final trace rc=$?"; grep "attention units by\|dsa_core\|pairs selected\|agreement" $OUT/final_trace_424242.log | cut -c1-900; tail -n 1 $OUT/final_trace_424242.log | cut -c1-2500
date
