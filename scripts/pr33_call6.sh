#!/bin/bash
# PR 33, chip call 6 (after the driver's refusal: the cell's runs
# spread too widely over the seeds), the committed files alone
# (.checkouts/final is `git archive $(git write-tree)`): the new cell
# with its experts placed (benchmark/builders/indexed_moe_lm.py
# place_experts) on a fresh seed, cold; on the seed that read slowest,
# traced (the rows by unit, the grouped products' time); on the seed
# that read fastest; a fourth seed while the budget lasts.
set +e
T0=$(date +%s)
OUT=$PWD/chiprun_out/pr33c
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.veles_cache/pr33_xla
unset JAX_COMPILATION_CACHE_MAX_SIZE
CELL=keye-vl2-ep8share.pretrain8k-1seq
cd .checkouts/final
date
for RUN in 1500000007:0 777000111:1 3000000033:0 2147486661:0; do
  SEED=${RUN%:*}; TRACE=${RUN#*:}
  SPENT=$(( $(date +%s) - T0 ))
  if [ $SPENT -gt 1920 ]; then echo "no time left for seed $SEED"; continue; fi
  timeout -k 10 $(( 2290 - SPENT )) python3 benchmark/run.py --workload $CELL --seed $SEED --seconds 20 --trace $TRACE > $OUT/run_$SEED.log 2>&1
  echo "run $SEED trace=$TRACE rc=$?"
  grep "experts placed\|agreement\|set-up\|window:\|grouped products\|expert rows\|roofline:" $OUT/run_$SEED.log | cut -c1-1700
  tail -n 1 $OUT/run_$SEED.log | cut -c1-3000
  date
done
