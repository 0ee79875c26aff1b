# usage: controls.sh <tag> <seconds> <base seed> <fault> <cell>...
# Runs each cell with a fault planted (benchmark/tests/control_run.py) on
# three seeds; `correct` has to read false in every line.
tag=$1; secs=$2; base=$3; fault=$4; shift 4
mkdir -p chiprun_out/$tag
for cell in "$@"; do for k in 1 2 3; do s=$((base+k));
 python3 benchmark/tests/control_run.py --fault $fault --workload $cell --seed $s --seconds $secs --trace 0 > chiprun_out/$tag/${fault}_${cell}_$s.out 2> chiprun_out/$tag/${fault}_${cell}_$s.err
 echo "$fault $cell seed $s rc $? $(tail -1 chiprun_out/$tag/${fault}_${cell}_$s.out | python3 -c 'import sys,json
try:
    r=json.loads(sys.stdin.read()); print("correct", r["correct"], {k:v["value"] for k,v in r["checks"].items() if v["value"]})
except Exception as e: print("no result", e)')"; done; done
