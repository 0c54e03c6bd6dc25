#!/bin/bash
# PR 37, chip call 3 (one chip), the committed files alone
# (.checkouts/final is `git archive $(git write-tree)` of the final
# tree): the new cell on six seeds of their own, untraced, the first
# of them cold, for the spread of its end-to-end metrics and for
# `correct` under the limits as committed. A record of the call as it
# ran.
set +e
T0=$(date +%s)
OUT=$PWD/chiprun_out/pr37c
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.veles_cache/pr37_xla
unset JAX_COMPILATION_CACHE_MAX_SIZE
CELL=lfm2-8b-a1b-ep4share.pretrain8k-1seq
cd .checkouts/final
for S in 2147483801 2147483802 2147483803 2147483804 2147483805 2147483806; do
  python3 benchmark/run.py --workload $CELL --seed $S --seconds 20 --trace 0 > $OUT/run_$S.log 2>&1
  echo "== run $S rc=$? at $(( $(date +%s) - T0 )) s"; grep "agreement\|set-up:\|window:\|experts placed" $OUT/run_$S.log | cut -c1-1300; tail -n 1 $OUT/run_$S.log | cut -c1-900
done
echo "done at $(( $(date +%s) - T0 )) s"
