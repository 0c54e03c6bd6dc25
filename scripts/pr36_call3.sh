#!/bin/bash
# PR 36, call 3 (one chip): `alexnet227.resident`, which runs
# FusedTrainer and never enters parallel/dp.py, to show it unmoved:
# change, parent, parent, change on two seeds, untraced; before them
# scripts/pr36_slice_peak.py (what the strided slices of the resident
# placement cost the loader's device). The change is the working tree
# at the root. A record of the call as it ran.
set +e
TOP=$PWD
T0=$(date +%s)
OUT=$PWD/chiprun_out/pr36c
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.veles_cache/pr36_xla}
CELL=alexnet227.resident
run() {  # side dir seed latest-start
  if [ $(( $(date +%s) - T0 )) -gt ${4:-9999} ]; then echo "skipped $1 $3: $(( $(date +%s) - T0 )) s gone"; return; fi
  cd $2
  timeout -k 10 420 python3 benchmark/run.py --workload $CELL --seed $3 --seconds 20 --trace 0 > $OUT/run_$1_$3.log 2>&1
  echo "== run $1 seed $3 rc=$? at $(( $(date +%s) - T0 )) s"
  grep "set-up\|window:" $OUT/run_$1_$3.log | cut -c1-220
  tail -n 1 $OUT/run_$1_$3.log | grep -o '"metrics".*' | cut -c1-600
  cd $TOP
}
timeout -k 10 200 python3 scripts/pr36_slice_peak.py 2>&1 | grep -v "^W0\|^I0" | tail -n 9
echo "== probe done at $(( $(date +%s) - T0 )) s"
run change $TOP 2147483911
run parent $TOP/.checkouts/parent 2147483911 500
run parent $TOP/.checkouts/parent 2147483912 620
run change $TOP 2147483912 700
echo "done at $(( $(date +%s) - T0 )) s"
