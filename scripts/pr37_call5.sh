#!/bin/bash
# PR 37, chip call 5 (one chip): one cell that was there,
# `alexnet227.resident`, traced, on the parent with this PR's
# benchmark files laid over it (.checkouts/overlay) and on the
# committed files (.checkouts/final): what this PR adds to the
# benchmark leaves an old cell's traced run as it was on both sides.
# A record of the call as it ran.
set +e
TOP=$PWD
T0=$(date +%s)
OUT=$PWD/chiprun_out/pr37e
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.veles_cache/pr37_xla
unset JAX_COMPILATION_CACHE_MAX_SIZE
for SIDE in overlay final; do
  cd $TOP/.checkouts/$SIDE
  python3 benchmark/run.py --workload alexnet227.resident --seed 2147483821 --seconds 20 --trace 1 > $OUT/trace_$SIDE.log 2>&1
  echo "== alexnet227.resident traced, $SIDE rc=$? at $(( $(date +%s) - T0 )) s"
  tail -n 1 $OUT/trace_$SIDE.log | grep -o '"correct".*' | sed 's/"breakdown".*"metrics"/"metrics"/' | cut -c1-1800
done
echo "done at $(( $(date +%s) - T0 )) s"
