# Parent against change on one chip in one call: for each cell, parent, change,
# change, parent at --trace 0 (a seed a pair), then one --trace 1 run a side.
#   git archive <parent> | tar -x -C .chipcheck/parent      (.chipcheck/ is in .gitignore)
#   chiprun --chips 1 --timeout 3000 -- bash benchmarks/tests/chip_pairs.sh .chipcheck/parent <seconds> <cell>...
# Appends "<side> <cell> <seed> <trace> <final line>" to chiprun_out/pairs.txt
# and each run's checks to chiprun_out/pairs.checks.
parent=$1; secs=$2; shift 2
here=$PWD; mkdir -p chiprun_out; n=0
one() {  # side cell seed trace
  dir=$here; [ $1 = parent ] && dir=$here/$parent
  (cd $dir && python3 benchmarks/run.py --workload $2 --seed $3 --seconds $secs --trace $4) > chiprun_out/run.log 2>&1
  echo "rc=$? $1 $2 seed=$3 trace=$4 $(grep -c FAILED chiprun_out/run.log) failed checks"
  echo "$1 $2 $3 $4 $(tail -1 chiprun_out/run.log)" >> chiprun_out/pairs.txt
  grep -H "^check\|^clock" chiprun_out/run.log | sed "s/^[^:]*:/$1 $2 $3 $4 /" >> chiprun_out/pairs.checks
  tail -1 chiprun_out/run.log | cut -c1-300
}
for cell in "$@"; do
  n=$((n + 2)); a=$((2147485000 + n)); b=$((a + 1))
  one parent $cell $a 0; one change $cell $a 0; one change $cell $b 0; one parent $cell $b 0
  one parent $cell $a 1; one change $cell $a 1
done
