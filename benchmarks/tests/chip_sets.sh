# The runs the bounds stand on: two sets of six runs of one cell, the same six
# seeds in both, in one call to the chip.
#   chiprun --chips 1 --timeout 3000 -- bash benchmarks/tests/chip_sets.sh <cell> <seconds> [<checkout>]
# Appends each run's final line to chiprun_out/sets_<cell>.jsonl and the
# numbers compared to chiprun_out/sets_<cell>.checks, and keeps each run's
# output under chiprun_out/logs/.  <checkout>: another tree to run from (the
# parent, unpacked under .chipcheck/); its files are tagged with its name.
cell=$1; secs=$2; dir=${3:-.}; tag=$cell; [ $dir = . ] || tag=$cell.$(basename $dir)
out=$PWD/chiprun_out; mkdir -p $out/logs
for set in 1 2; do for i in 1 2 3 4 5 6; do
  seed=$((2147486000 + i)); log=$out/logs/$tag.$set.$seed.log
  (cd $dir && python3 benchmarks/run.py --workload $cell --seed $seed --seconds $secs --trace 0) > $log 2>&1
  echo "rc=$? set=$set seed=$seed $(grep -c FAILED $log) failed checks"
  tail -1 $log | tee -a $out/sets_$tag.jsonl | cut -c1-400
  grep "^check" $log >> $out/sets_$tag.checks
done; done
