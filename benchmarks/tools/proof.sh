# Builder's tool, run on the chip: two sets of six runs of one cell on the same seeds, then one traced run.
# The result lines go to chiprun_out/proof/<tag>.txt; PERF.md section 6 holds the spreads read from them.
# usage: _proof.sh <cell> <seed base> <tag>
cell=$1; base=$2; tag=$3
mkdir -p chiprun_out/logs chiprun_out/proof
for set in A B; do
for k in 1 2 3 4 5 6; do
seed=$((base + k))
python3 benchmarks/run.py --workload $cell --seed $seed --seconds 40 --trace 0 > chiprun_out/logs/${tag}_${set}$k.out 2> chiprun_out/logs/${tag}_${set}$k.err; rc=$?
echo "PROOF $cell set=$set seed=$seed rc=$rc $(tail -n 1 chiprun_out/logs/${tag}_${set}$k.out)" | tee -a chiprun_out/proof/$tag.txt
grep -E "^window" chiprun_out/logs/${tag}_${set}$k.out | cut -c1-600; grep -c "warm round" chiprun_out/logs/${tag}_${set}$k.out; tail -c 200 chiprun_out/logs/${tag}_${set}$k.err
done
done
python3 benchmarks/run.py --workload $cell --seed $((base + 7)) --seconds 40 --trace 1 > chiprun_out/logs/${tag}_T.out 2> chiprun_out/logs/${tag}_T.err; echo rc=$?
echo "TRACED $cell $(tail -n 1 chiprun_out/logs/${tag}_T.out)" | tee -a chiprun_out/proof/$tag.txt
grep -E "^set-up|^window|traced replay" chiprun_out/logs/${tag}_T.out | cut -c1-700
