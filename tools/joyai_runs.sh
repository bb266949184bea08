#!/bin/bash
# usage: tools/joyai_runs.sh <tag> <trace seed or -> <untraced seeds...>
# runs the JoyAI cell (or the cell $CELL names) on the chip, one process a
# run, outputs under chiprun_out/, each run's wall seconds printed; $DIR: the
# checkout to run (default this one; e.g. an unpacked parent commit)
tag=$1; traced=$2; shift 2
out=$(pwd)/chiprun_out; mkdir -p $out
CELL=${CELL:-joyai_llm_flash_lm_mtp_s8192}
run() {  # seed trace name
  local t0=$(date +%s)
  (cd ${DIR:-.} && python3 benchmark/run.py --workload $CELL --seed $1 --seconds 20 --trace $2) > $out/$3.txt 2> $out/$3.err
  local rc=$?
  echo "$([ $2 = 1 ] && echo traced || echo seed) $1 rc=$rc wall_s=$(( $(date +%s) - t0 ))"
}
if [ "$traced" != "-" ]; then
  run $traced 1 ${tag}_t${traced}; tail -n 1 $out/${tag}_t${traced}.txt | cut -c1-3000
  (cd ${DIR:-.} && python3 tools/trace_by_op.py $CELL) > $out/${tag}_byop.txt 2>&1
fi
for s in "$@"; do
  run $s 0 ${tag}_s${s}; tail -n 1 $out/${tag}_s${s}.txt | cut -c1-600
done
