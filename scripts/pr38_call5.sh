#!/bin/bash
# PR 38, chip call 5 (one chip): the committed files alone
# (.checkouts/final, `git archive $(git write-tree)`) on the two token
# cells no other call of this PR ran, each cold and traced (is it
# `correct`, does its per-program table hold a build by the harvest,
# how long is a cold start), and on alexnet227.resident untraced (the
# end-to-end metrics). The parent's side of these is the ledger's. A
# record of the call as it ran.
set +e
TOP=$PWD
T0=$(date +%s)
OUT=$TOP/chiprun_out/pr38e
mkdir -p $OUT
unset JAX_COMPILATION_CACHE_MAX_SIZE
export JAX_COMPILATION_CACHE_DIR=$TOP/.veles_cache/pr38_xla_final
ls $TOP/.checkouts/final/benchmark/run.py || exit 2
cd $TOP/.checkouts/final
run() {  # cell seed trace
  timeout -k 10 1100 python3 benchmark/run.py --workload $1 --seed $2 --seconds 20 --trace $3 > $OUT/$1.log 2>$OUT/$1.err
  echo "== $1: seed $2 trace $3 rc=$? at $(( $(date +%s) - T0 )) s"
  grep "^set-up:\|^window:" $OUT/$1.log | cut -c1-200
  tail -n 1 $OUT/$1.log | grep -o '"correct".*' | sed 's/"breakdown".*"metrics"/"metrics"/' | cut -c1-400
  tail -n 1 $OUT/$1.log | grep -o '"setup_data_stage_s".*' | cut -c1-700
  grep "^head " $OUT/$1.log | cut -c1-300
  sed -n '/^by program, s:/,/more programs under/p' $OUT/$1.log | cut -c1-160 | head -n 5
}
run glm47flash-ep8share.pretrain4k 2147485501 1
run laguna-s21-ep32share.pretrain-1seq 2147485502 1
run alexnet227.resident 2147485503 0
echo "done at $(( $(date +%s) - T0 )) s"
