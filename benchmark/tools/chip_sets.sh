#!/bin/sh
# two sets of runs of one cell with the same seeds (the contract's measure of
# spread), then traced runs: sh benchmark/tools/chip_sets.sh <cell> "<seeds>" "<trace seeds>"
# then: python3 benchmark/tools/spreads.py chiprun_out/sets_<cell>.jsonl
cell=$1; seeds=$2; traced=$3
mkdir -p chiprun_out/runs
out=chiprun_out/sets_$cell.jsonl; : > $out
run() {  # set, seed, trace
  log=chiprun_out/runs/${cell}_$1_$2
  python3 benchmark/run.py --workload $cell --seed $2 --trace $3 > $log.txt 2> $log.err
  rc=$?
  echo "set $1 seed $2 rc=$rc $(grep -E '^setup|^check' $log.txt | tr '\n' ';')"
  [ $rc -ne 0 ] && tail -n 12 $log.err
  echo "{\"set\": \"$1\", \"seed\": $2, \"rc\": $rc, \"line\": $(tail -n 1 $log.txt)}" >> $out
}
for set in 1 2; do
  for seed in $seeds; do
    run $set $seed 0
    tail -n 1 chiprun_out/runs/${cell}_${set}_$seed.txt | cut -c1-420
  done
done
for seed in $traced; do
  run trace $seed 1
  tail -n 1 chiprun_out/runs/${cell}_trace_$seed.txt | cut -c1-2500
done
python3 benchmark/tools/spreads.py $out
