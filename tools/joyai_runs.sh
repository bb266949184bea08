#!/bin/bash
# usage: tools/joyai_runs.sh <tag> <trace seed or -> <untraced seeds...>
# runs the JoyAI cell (or the cell $CELL names) on the chip, one process a
# run, outputs under chiprun_out/
tag=$1; traced=$2; shift 2
mkdir -p chiprun_out
CELL=${CELL:-joyai_llm_flash_lm_mtp_s8192}
if [ "$traced" != "-" ]; then
  python3 benchmark/run.py --workload $CELL --seed $traced --seconds 20 --trace 1 > chiprun_out/${tag}_t${traced}.txt 2> chiprun_out/${tag}_t${traced}.err
  echo "traced $traced rc=$?"; tail -n 1 chiprun_out/${tag}_t${traced}.txt | cut -c1-3000
  python3 tools/trace_by_op.py $CELL > chiprun_out/${tag}_byop.txt 2>&1
fi
for s in "$@"; do
  python3 benchmark/run.py --workload $CELL --seed $s --seconds 20 --trace 0 > chiprun_out/${tag}_s${s}.txt 2> chiprun_out/${tag}_s${s}.err
  echo "seed $s rc=$?"; tail -n 1 chiprun_out/${tag}_s${s}.txt | cut -c1-600
done
