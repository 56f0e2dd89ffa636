# The runs the bounds were set from: two sets of six runs of one cell, the
# same six seeds in both, in one call to the chip.
#   chiprun --chips 1 --timeout 3000 -- bash benchmarks/tests/chip_sets.sh <cell> <seconds>
# Appends each run's final line to chiprun_out/sets_<cell>.jsonl and the
# numbers compared to chiprun_out/sets_<cell>.checks.
cell=$1; secs=$2
mkdir -p chiprun_out
for set in 1 2; do for i in 1 2 3 4 5 6; do
  seed=$((2147484000 + i))
  python3 benchmarks/run.py --workload $cell --seed $seed --seconds $secs --trace 0 > chiprun_out/run.log 2>&1
  echo "rc=$? set=$set seed=$seed $(grep -c FAILED chiprun_out/run.log) failed checks"
  tail -1 chiprun_out/run.log | tee -a chiprun_out/sets_$cell.jsonl | cut -c1-400
  grep "^check" chiprun_out/run.log >> chiprun_out/sets_$cell.checks
done; done
