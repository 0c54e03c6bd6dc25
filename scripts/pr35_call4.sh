#!/bin/bash
# PR 35, call 4 (one chip): the committed files alone
# (.checkouts/final is `git archive $(git write-tree)` of the final
# tree): alexnet227.resident cold and warm, traced, then one token
# cell, glm47flash-ep8share.pretrain4k, cold, traced. A record of the
# call as it ran.
set +e
TOP=$PWD
T0=$(date +%s)
OUT=$PWD/chiprun_out/pr35d
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.veles_cache/pr35_xla
unset JAX_COMPILATION_CACHE_MAX_SIZE
cd $TOP/.checkouts/final
run() {  # name cell seed
  timeout -k 10 1200 python3 benchmark/run.py --workload $2 --seed $3 --seconds 20 --trace 1 > $OUT/$1.log 2>$OUT/$1.err
  echo "== $1: $2 seed $3 rc=$? at $(( $(date +%s) - T0 )) s"
  grep "^set-up:\|^window:\|^checks:" $OUT/$1.log | cut -c1-420
  tail -n 1 $OUT/$1.log | grep -o '"setup_data_stage_s".*' | cut -c1-700
  grep "^head \|first steady epoch" $OUT/$1.log | cut -c1-300
}
run cold alexnet227.resident 2147484201
run warm alexnet227.resident 2147484202
run glm_cold glm47flash-ep8share.pretrain4k 2147484203
echo "-- the GLM table"
sed -n '/^set-up by the program/,/^head /p' $OUT/glm_cold.log | cut -c1-150 | head -n 110
echo "done at $(( $(date +%s) - T0 )) s"
