#!/bin/bash
# PR 35, call 6 (one chip): is the one-step segment of the Keye cell
# slower on the final tree (call 5 read 100.6 s warm where call 2 read
# 82.1-83.6 on parent and change alike)? One machine: the parent cold
# (fills the cache), the final tree warm, the parent warm, the final
# tree warm from the root; untraced. A record of the call as it ran.
set +e
TOP=$PWD
T0=$(date +%s)
OUT=$PWD/chiprun_out/pr35f
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.veles_cache/pr35_xla
unset JAX_COMPILATION_CACHE_MAX_SIZE
CELL=keye-vl2-ep8share.pretrain8k-1seq
run() {  # name dir seed latest-start
  if [ $(( $(date +%s) - T0 )) -gt $4 ]; then echo "skipped $1: $(( $(date +%s) - T0 )) s gone"; return; fi
  cd $2
  timeout -k 10 1500 python3 benchmark/run.py --workload $CELL --seed $3 --seconds 20 --trace 0 > $OUT/$1.log 2>$OUT/$1.err
  echo "== $1: $2 seed $3 rc=$? at $(( $(date +%s) - T0 )) s"
  grep "^set-up:\|one-step segment ran\|^warm-up" $OUT/$1.log | cut -c1-160
  tail -n 1 $OUT/$1.log | grep -o '"metrics".*' | cut -c1-400
  cd $TOP
}
run parent_cold $TOP/.checkouts/parent 2147484401 0
run final_warm $TOP/.checkouts/final 2147484402 1300
run parent_warm $TOP/.checkouts/parent 2147484402 1650
run root_warm $TOP 2147484403 1700
echo "done at $(( $(date +%s) - T0 )) s"
