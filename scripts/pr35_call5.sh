#!/bin/bash
# PR 35, call 5 (one chip): keye-vl2-ep8share.pretrain8k-1seq again,
# cold and warm, traced, on the committed files of the final code
# (.checkouts/final): call 2 ran before a stage inside a stage was
# folded into it. A record of the call as it ran.
set +e
TOP=$PWD
T0=$(date +%s)
OUT=$PWD/chiprun_out/pr35e
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.veles_cache/pr35_xla
unset JAX_COMPILATION_CACHE_MAX_SIZE
CELL=keye-vl2-ep8share.pretrain8k-1seq
cd $TOP/.checkouts/final
run() {  # name seed
  timeout -k 10 1500 python3 benchmark/run.py --workload $CELL --seed $2 --seconds 20 --trace 1 > $OUT/$1.log 2>$OUT/$1.err
  echo "== $1: seed $2 rc=$? at $(( $(date +%s) - T0 )) s"
  grep "^set-up:\|^window:\|^checks:" $OUT/$1.log | cut -c1-420
  tail -n 1 $OUT/$1.log | grep -o '"setup_data_stage_s".*' | cut -c1-700
  grep "^head \|first steady epoch" $OUT/$1.log | cut -c1-300
}
run cold 2147484301
run warm 2147484302
echo "-- the warm table"
sed -n '/^set-up by the program/,/^head /p' $OUT/warm.log | cut -c1-150 | head -n 110
echo "done at $(( $(date +%s) - T0 )) s"
