#!/bin/bash
# PR 35, call 7 (one chip, the PR's last 6 chip-minutes): the Keye
# cell's one-step train segment traced and lowered (no compile) by
# scripts/pr35_onestep.py on the final tree, on the final tree with
# the train call site inline as it was before the clean-up
# (.checkouts/noc), and on the parent if time is left. A record.
set +e
T0=$(date +%s)
OUT=$PWD/chiprun_out/pr35g
mkdir -p $OUT
for SIDE in final noc parent; do
  if [ $(( $(date +%s) - T0 )) -gt 190 ]; then echo "skipped $SIDE"; continue; fi
  timeout -k 5 140 python3 scripts/pr35_onestep.py $PWD/.checkouts/$SIDE --cell keye-vl2-ep8share.pretrain8k-1seq > $OUT/$SIDE.log 2>&1
  echo "== $SIDE rc=$? at $(( $(date +%s) - T0 )) s"
  grep "^one-step\|workflow and trainer" $OUT/$SIDE.log | cut -c1-300
done
