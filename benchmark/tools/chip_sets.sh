#!/bin/sh
# two sets of runs of one cell with the same seeds (the contract's measure of
# spread), then traced runs: sh benchmark/tools/chip_sets.sh <cell> "<seeds>" "<trace seeds>"
cell=$1; seeds=$2; traced=$3
mkdir -p chiprun_out
out=chiprun_out/sets_$cell.jsonl; : > $out
for set in 1 2; do
  for seed in $seeds; do
    python3 benchmark/run.py --workload $cell --seed $seed --trace 0 \
        > chiprun_out/last_run.txt 2> chiprun_out/last_run.err
    rc=$?
    echo "set $set seed $seed rc=$rc $(grep -E '^setup|^check' chiprun_out/last_run.txt | tr '\n' ';')"
    [ $rc -ne 0 ] && tail -n 12 chiprun_out/last_run.err
    echo "{\"set\": $set, \"seed\": $seed, \"rc\": $rc, \"line\": $(tail -n 1 chiprun_out/last_run.txt)}" >> $out
    tail -n 1 chiprun_out/last_run.txt | cut -c1-420
  done
done
for seed in $traced; do
  python3 benchmark/run.py --workload $cell --seed $seed --trace 1 \
      > chiprun_out/last_run.txt 2> chiprun_out/last_run.err
  rc=$?
  echo "traced seed $seed rc=$rc"
  [ $rc -ne 0 ] && tail -n 12 chiprun_out/last_run.err
  echo "{\"set\": \"trace\", \"seed\": $seed, \"rc\": $rc, \"line\": $(tail -n 1 chiprun_out/last_run.txt)}" >> $out
  tail -n 1 chiprun_out/last_run.txt | cut -c1-2500
  grep "^counter" chiprun_out/last_run.txt | tr '\n' ';'; echo
done
