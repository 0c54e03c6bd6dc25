#!/bin/bash
# PR 37, chip call 2 (one chip), the committed files alone
# (.checkouts/final is `git archive $(git write-tree)`): the new cell
# with the head of 64 behind zeros through the banded kernels and the
# table filled at std 0.006, cold and traced; the tolerance probe (the
# int8 reference) on the same seed; two more seeds untraced, warm.
# A record of the call as it ran.
set +e
T0=$(date +%s)
OUT=$PWD/chiprun_out/pr37b
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.veles_cache/pr37_xla
unset JAX_COMPILATION_CACHE_MAX_SIZE
CELL=lfm2-8b-a1b-ep4share.pretrain8k-1seq
SEED=2147483777
cd .checkouts/final
python3 benchmark/run.py --workload $CELL --seed $SEED --seconds 20 --trace 1 > $OUT/trace_$SEED.log 2>&1
echo "== trace rc=$? at $(( $(date +%s) - T0 )) s"
grep "agreement\|set-up:\|window:\|loss:\|checks:\|experts placed\|roofline:\|mixers by\|takes XLA" $OUT/trace_$SEED.log | cut -c1-1500
sed -n '/units of train_segment/,/head and loss by stream/p' $OUT/trace_$SEED.log | cut -c1-400
tail -n 1 $OUT/trace_$SEED.log | cut -c1-3500
python3 scripts/lm_tolerance_probe.py --cell $CELL --seed $SEED > $OUT/probe_$SEED.log 2>&1
echo "== probe rc=$? at $(( $(date +%s) - T0 )) s"; grep "^control program\|^control int8\|^int8\|Error" $OUT/probe_$SEED.log | cut -c1-1200
for S in 3000000037 77770037; do
  python3 benchmark/run.py --workload $CELL --seed $S --seconds 20 --trace 0 > $OUT/run_$S.log 2>&1
  echo "== run $S rc=$? at $(( $(date +%s) - T0 )) s"; grep "agreement\|set-up:\|window:\|checks:" $OUT/run_$S.log | cut -c1-1300; tail -n 1 $OUT/run_$S.log | cut -c1-900
done
echo "done at $(( $(date +%s) - T0 )) s"
