#!/bin/bash
# PR 37, chip call 4 (one chip): nothing moved in the cells that
# were there. `laguna-s21-ep32share.pretrain-1seq` (it shares
# `GroupedAttentionForward`, `causal_attention`, `nn/moe.py`,
# `nn/tokens.py` and the step's head call with the new cell) parent,
# change, change, parent on two seeds, and `alexnet227.resident`
# parent, change; untraced. The parent is `git archive` of 96a2645 in
# .checkouts/parent, the change the committed files in
# .checkouts/final; both lower to one program, so the first run
# compiles for all four. A record of the call as it ran.
set +e
TOP=$PWD
T0=$(date +%s)
OUT=$PWD/chiprun_out/pr37d
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.veles_cache/pr37_xla
unset JAX_COMPILATION_CACHE_MAX_SIZE
run() {  # side cell seed
  cd $TOP/.checkouts/$1
  python3 benchmark/run.py --workload $2 --seed $3 --seconds 20 --trace 0 > $OUT/run_$2_$1_$3.log 2>&1
  echo "== $2 $1 seed $3 rc=$? at $(( $(date +%s) - T0 )) s"
  grep "set-up:\|window:" $OUT/run_$2_$1_$3.log | cut -c1-300
  tail -n 1 $OUT/run_$2_$1_$3.log | grep -o '"correct".*' | cut -c1-700
  cd $TOP
}
run parent laguna-s21-ep32share.pretrain-1seq 2147483811
run final laguna-s21-ep32share.pretrain-1seq 2147483811
run final laguna-s21-ep32share.pretrain-1seq 2147483812
run parent laguna-s21-ep32share.pretrain-1seq 2147483812
run parent alexnet227.resident 2147483813
run final alexnet227.resident 2147483813
echo "done at $(( $(date +%s) - T0 )) s"
