#!/bin/bash
# PR 33, chip call 5 (after the review), the committed files alone
# (.checkouts/final is `git archive $(git write-tree)`): the search for
# a block's keys against lax.top_k; the new cell traced on a seed that
# read slow and on one that read fast (whose operation follows the
# seed, and the rows behind it), each trace then split by operation;
# fresh seeds untraced while the budget lasts.
set +e
T0=$(date +%s)
OUT=$PWD/chiprun_out/pr33b
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.veles_cache/pr33_xla
unset JAX_COMPILATION_CACHE_MAX_SIZE
CELL=keye-vl2-ep8share.pretrain8k-1seq
cd .checkouts/final
date
python3 scripts/select_keys_bench.py > $OUT/select_keys_bench.log 2>&1
echo "select_keys_bench rc=$?"; grep -v Warn $OUT/select_keys_bench.log | tail -n 5
date
for SEED in 777000111 3000000033; do
  rm -f chiprun_out/route_by_operation.txt
  python3 benchmark/run.py --workload $CELL --seed $SEED --seconds 20 --trace 1 > $OUT/trace_$SEED.log 2>&1
  echo "trace $SEED rc=$?"
  grep "agreement\|set-up\|window:\|grouped products\|expert rows\|roofline:" $OUT/trace_$SEED.log | cut -c1-1700
  tail -n 1 $OUT/trace_$SEED.log | cut -c1-3000
  python3 scripts/route_by_operation.py --cell $CELL --steps 16 > $OUT/route_$SEED.log 2>&1
  echo "route $SEED rc=$?"; cp chiprun_out/route_by_operation.txt $OUT/route_$SEED.txt
  date
done
for SEED in 2147485555 1500000007; do
  if [ $(( $(date +%s) - T0 )) -gt 1880 ]; then echo "no time left for seed $SEED"; continue; fi
  python3 benchmark/run.py --workload $CELL --seed $SEED --seconds 20 --trace 0 > $OUT/run_$SEED.log 2>&1
  echo "run $SEED rc=$?"; grep "agreement\|set-up\|window:" $OUT/run_$SEED.log | cut -c1-1700; tail -n 1 $OUT/run_$SEED.log
  date
done
