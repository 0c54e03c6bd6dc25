#!/bin/bash
# PR 37, chip call 1 (one chip): the parent, with this PR's benchmark
# files laid over it, on the new cell (it has to fail at once); the
# tiny configuration's trace fixture; the new cell cold and traced;
# the tolerance probe (the int8 reference) on the same seed. A record
# of the call as it ran.
set +e
TOP=$PWD
T0=$(date +%s)
OUT=$PWD/chiprun_out/pr37
mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.veles_cache/pr37_xla
unset JAX_COMPILATION_CACHE_MAX_SIZE
CELL=lfm2-8b-a1b-ep4share.pretrain8k-1seq
SEED=2147483777
cd .checkouts/parent
timeout -k 10 300 python3 benchmark/run.py --workload $CELL --seed $SEED --seconds 20 --trace 0 > $OUT/parent_new_cell.log 2>&1
echo "== parent on the new cell rc=$? at $(( $(date +%s) - T0 )) s"; grep -v "^W0\|^I0" $OUT/parent_new_cell.log | tail -n 4 | cut -c1-300
cd $TOP
python3 benchmark/tests/record_conv_lm.py $OUT/tiny-conv.v5e-1.xplane.pb > $OUT/record.log 2>&1
echo "== record rc=$? at $(( $(date +%s) - T0 )) s"; tail -n 2 $OUT/record.log | cut -c1-900
python3 benchmark/run.py --workload $CELL --seed $SEED --seconds 20 --trace 1 > $OUT/trace_$SEED.log 2>&1
echo "== trace rc=$? at $(( $(date +%s) - T0 )) s"; grep -v "^W0\|^I0" $OUT/trace_$SEED.log | tail -n 75 | cut -c1-1800
python3 scripts/lm_tolerance_probe.py --cell $CELL --seed $SEED > $OUT/probe_$SEED.log 2>&1
echo "== probe rc=$? at $(( $(date +%s) - T0 )) s"; grep "^control\|^int8\|Error\|error" $OUT/probe_$SEED.log | cut -c1-1200
echo "done at $(( $(date +%s) - T0 )) s"
