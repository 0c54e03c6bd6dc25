#!/bin/bash
# PR 33, chip call 2: the parent given the new cell, the tolerance
# probe, four more seeds, one old cell on both sides.
set +e
OUT=$PWD/chiprun_out/pr33
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.veles_cache/pr33_xla
unset JAX_COMPILATION_CACHE_MAX_SIZE
CELL=keye-vl2-ep8share.pretrain8k-1seq
date
# the parent under this PR's benchmark files
cp BENCHMARK.json .checkouts/parent/BENCHMARK.json
cp -r benchmark/. .checkouts/parent/benchmark/
(cd .checkouts/parent; T0=$(date +%s); python3 benchmark/run.py --workload $CELL --seed 5 --seconds 20 --trace 1 > $OUT/parent_newcell.log 2>&1; echo "parent new cell rc=$? after $(( $(date +%s) - T0 )) s"; tail -n 3 $OUT/parent_newcell.log | cut -c1-400)
date
python3 scripts/lm_tolerance_probe.py --cell $CELL --seed 99991033 > $OUT/probe_99991033.log 2>&1
echo "probe rc=$?"; grep "^control\|int8:" $OUT/probe_99991033.log | cut -c1-1800
date
for SEED in 1234567891 2000000011 777000111 31337; do
  python3 benchmark/run.py --workload $CELL --seed $SEED --seconds 20 --trace 0 > $OUT/run_$SEED.log 2>&1
  echo "run $SEED rc=$?"; grep "agreement\|set-up" $OUT/run_$SEED.log | cut -c1-1500; tail -n 1 $OUT/run_$SEED.log
  date
done
# one old cell, traced, parent (overlaid) then change: the same programs
(cd .checkouts/parent; python3 benchmark/run.py --workload alexnet227.resident --seed 2147480033 --seconds 20 --trace 1 > $OUT/parent_alexnet_trace.log 2>&1; echo "parent alexnet trace rc=$?"; tail -n 1 $OUT/parent_alexnet_trace.log | cut -c1-1500)
date
python3 benchmark/run.py --workload alexnet227.resident --seed 2147480033 --seconds 20 --trace 0 > $OUT/change_alexnet.log 2>&1; echo "change alexnet rc=$?"; tail -n 1 $OUT/change_alexnet.log
(cd .checkouts/parent; python3 benchmark/run.py --workload alexnet227.resident --seed 2147480033 --seconds 20 --trace 0 > $OUT/parent_alexnet.log 2>&1; echo "parent alexnet rc=$?"; tail -n 1 $OUT/parent_alexnet.log)
date
