# usage: sets.sh <cell> <tag> <seconds> <base seed>
cell=$1; tag=$2; secs=$3; base=$4
mkdir -p chiprun_out/$tag
# both sets run seeds base+1..6: the driver's two sets have the same
# seeds, and the spreads that set a bound are read so (PERF.md, PR 29)
for set in 1 2; do for k in 1 2 3 4 5 6; do s=$((base+k));
 python3 benchmark/run.py --workload $cell --seed $s --seconds $secs --trace 0 > chiprun_out/$tag/s${set}_$s.out 2> chiprun_out/$tag/s${set}_$s.err; echo "set$set seed $s rc $? $(tail -1 chiprun_out/$tag/s${set}_$s.out | cut -c1-260)"; done; done
for k in 7 8 9; do s=$((base+k)); python3 benchmark/run.py --workload $cell --seed $s --seconds $secs --trace 0 > chiprun_out/$tag/x_$s.out 2> chiprun_out/$tag/x_$s.err; echo "extra seed $s rc $? $(tail -1 chiprun_out/$tag/x_$s.out | cut -c1-260)"; done
for k in 10 11 12; do s=$((base+k)); python3 benchmark/run.py --workload $cell --seed $s --seconds $secs --trace 1 > chiprun_out/$tag/t_$s.out 2> chiprun_out/$tag/t_$s.err; echo "trace seed $s rc $? $(tail -1 chiprun_out/$tag/t_$s.out | cut -c1-1200)"; done
