# Two sets of 6 runs of one cell with the same seeds in both, then 3 traced runs:
#   chiprun --timeout 3000 -- bash perfbench/tools/sets.sh <cell> <seconds> <first seed>
# One line a run in chiprun_out/sets_<cell>.jsonl: {"set":..,"seed":..,"rc":..,"wall_s":..,"result":{...}}
W=$1; SECS=$2; S0=$3
mkdir -p chiprun_out
OUT=chiprun_out/sets_$W.jsonl
run() {  # set seed trace
  t0=$(date +%s)
  python3 perfbench/run.py --workload $W --seed $2 --seconds $SECS --trace $3 > chiprun_out/_run.out 2> chiprun_out/_run.err
  rc=$?
  t1=$(date +%s)
  line=$(tail -n 1 chiprun_out/_run.out)
  [ -z "$line" ] && line=null
  echo "{\"set\":\"$1\",\"seed\":$2,\"rc\":$rc,\"wall_s\":$(( t1 - t0 )),\"result\":$line}" >> $OUT
  [ $rc -ne 0 ] && tail -n 5 chiprun_out/_run.err | cut -c1-400
  echo "$1 $2 rc=$rc $(echo "$line" | cut -c1-260)"
}
for set in A B; do
  for i in 0 1 2 3 4 5; do run $set $(( S0 + 104729 * i )) 0; done
done
for i in 6 7 8; do run T $(( S0 + 104729 * i )) 1; done
rm -f chiprun_out/_run.out chiprun_out/_run.err
