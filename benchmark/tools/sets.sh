# usage: bash benchmark/tools/sets.sh <cell> <seconds> <first_seed> [runs_per_set]
# two sets of runs with the same seeds, one process per run, as the driver
# makes them; results to chiprun_out/sets_<cell>.jsonl
cell=$1; seconds=$2; first=$3; n=${4:-6}
mkdir -p chiprun_out
out=chiprun_out/sets_${cell}.jsonl
for set in 1 2; do
  for i in $(seq 0 $((n - 1))); do
    seed=$((first + 104729 * i))
    line=$(python3 benchmark/run.py --workload $cell --seed $seed --seconds $seconds --trace 0 2>> chiprun_out/sets_${cell}.err | tail -1)
    echo "{\"set\": $set, \"seed\": $seed, \"line\": ${line:-null}}" >> $out
  done
done
for i in 0 1 2; do
  seed=$((first + 7 + 104729 * i))
  line=$(python3 benchmark/run.py --workload $cell --seed $seed --seconds $seconds --trace 1 2>> chiprun_out/sets_${cell}.err | tail -1)
  echo "{\"set\": \"trace\", \"seed\": $seed, \"line\": ${line:-null}}" >> chiprun_out/trace_${cell}.jsonl
done
python3 benchmark/tools/spread.py $out
