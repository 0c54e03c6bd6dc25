#!/bin/bash
# PR 38, chip call 6 (one chip): the committed files of the final tree
# (.checkouts/final, `git archive $(git write-tree)`, made after the
# last edit of the code: `_signature` takes a host value) on
# alexnet227.resident, untraced, cold then warm: the end-to-end
# metrics. A record of the call as it ran.
set +e
TOP=$PWD
T0=$(date +%s)
OUT=$TOP/chiprun_out/pr38f
mkdir -p $OUT
unset JAX_COMPILATION_CACHE_MAX_SIZE
export JAX_COMPILATION_CACHE_DIR=$TOP/.veles_cache/pr38_xla_final
ls $TOP/.checkouts/final/benchmark/run.py || exit 2
cd $TOP/.checkouts/final
grep -c 'getattr(leaf, "sharding", None)' veles_tpu/train/step.py
for RUN in cold:2147485601 warm:2147485602; do
  timeout -k 10 600 python3 benchmark/run.py --workload alexnet227.resident --seed ${RUN#*:} --seconds 20 --trace 0 > $OUT/${RUN%:*}.log 2>$OUT/${RUN%:*}.err
  echo "== ${RUN%:*}: seed ${RUN#*:} rc=$? at $(( $(date +%s) - T0 )) s"
  grep "^set-up:\|^window:" $OUT/${RUN%:*}.log | cut -c1-200
  tail -n 1 $OUT/${RUN%:*}.log | grep -o '"correct".*' | cut -c1-500
done
echo "done at $(( $(date +%s) - T0 )) s"
