#!/bin/sh
# A cell of the benchmark on the parent commit and on this tree, in one chip
# call, so that both are measured on the same chip (PERF.md section 6, PR 42's
# pairs are this script's output).
#
#   sh scripts/pair_cell.sh prepare
#       here, where git is: the parent (HEAD) under this tree's benchmark
#       files, as the driver lays them, in .cache/pair/parent; what git would
#       commit of this tree (`git add` first) in .cache/pair/change.
#   chiprun --chips 1 --timeout 3400 -- sh scripts/pair_cell.sh run <cell> <seed> <traced> <pairs> <more> [<limit_s>]
#       on the chip: <traced> (0 or 1) traced pairs, then <pairs> times parent,
#       change, change, parent (two pairs, each its own seed, the two sides of a
#       pair sharing it), then <more> runs of the change alone, each its own seed.
#       <traced> "parts": the traced pair through chipbench/tools/token_table.py
#       (a token cell), which prints run.py's line and then the step by cell,
#       mixer and part, and keeps what the tables were made from in
#       chiprun_out/parts_<cell> (the change) and parts_<cell>.parent; the
#       printed tables go to chiprun_out/pairs/<cell>.<seed>.<side>.table.txt.
#       Seeds count up from <seed>. No run starts after <limit_s> seconds
#       (2700) and none is killed: a run cut while it holds the chip loses it.
#       Every run's last line goes to chiprun_out/pairs/<cell>.<seed>.jsonl
#       with its side, seed and wall seconds (a call's own file: the tool
#       replaces a file of the same name when it brings a call's output back).
set -u
root=$(cd "$(dirname "$0")/.." && pwd)
cd "$root"

if [ "$1" = prepare ]; then
    rm -rf .cache/pair && mkdir -p .cache/pair/parent .cache/pair/change
    git archive HEAD | tar -x -C .cache/pair/parent
    git archive "$(git write-tree)" | tar -x -C .cache/pair/change
    cp .cache/pair/change/BENCHMARK.json .cache/pair/parent/
    for path in $(python3 -c "import json; print(' '.join(json.load(open('BENCHMARK.json'))['paths']))"); do
        cp -r .cache/pair/change/"$path"/. .cache/pair/parent/"$path"/
    done
    exit 0
fi

cell=$2 seed=$3 traced=$4 pairs=$5 more=$6 limit=${7:-2700}
seconds=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
out=$root/chiprun_out/pairs
mkdir -p "$out"
began=$(date +%s)

one() {  # side seed trace
    now=$(( $(date +%s) - began ))
    if [ "$now" -gt "$limit" ]; then echo "SKIPPED $1 seed=$2 trace=$3 at ${now}s"; return; fi
    start=$(date +%s)
    if [ "$3" = parts ]; then
        kept=$root/chiprun_out/parts_$cell; [ "$1" = change ] || kept=$kept.$1
        (cd ".cache/pair/$1" && python3 chipbench/tools/token_table.py --workload "$cell" \
            --seed "$2" --seconds "$seconds" --dump "$kept") > "$out/last.out" 2> "$out/last.err"
        rc=$?
        # the result line, then the tables: the line last, where the next step reads it
        grep -v '^{"correct"' "$out/last.out" > "$out/$cell.$seed.$1.table.txt"
        grep '^{"correct"' "$out/last.out" > "$out/last.line" && cat "$out/last.line" >> "$out/last.out"
    else
        (cd ".cache/pair/$1" && python3 chipbench/run.py --workload "$cell" --seed "$2" \
            --seconds "$seconds" --trace "$3") > "$out/last.out" 2> "$out/last.err"
        rc=$?
    fi
    wall=$(( $(date +%s) - start ))
    echo "RAN $1 seed=$2 trace=$3 rc=$rc wall=${wall}s"
    tail -n 1 "$out/last.out"
    [ "$rc" = 0 ] || tail -n 30 "$out/last.err"
    python3 - "$1" "$2" "$3" "$rc" "$wall" "$out" "$cell.$seed" <<'EOF'
import json, sys
side, seed, trace, rc, wall, out, name = sys.argv[1:]
lines = open(f"{out}/last.out").read().strip().splitlines()
try:
    result = json.loads(lines[-1])
except (IndexError, ValueError):
    result = None
with open(f"{out}/{name}.jsonl", "a") as log:
    log.write(json.dumps({"side": side, "seed": int(seed), "trace": int(trace != "0"), "rc": int(rc),
                          "wall_s": int(wall), "result": result}) + "\n")
EOF
}

if [ "$traced" != 0 ]; then
    one parent "$seed" "$traced"
    one change "$seed" "$traced"
fi
n=0
while [ "$n" -lt "$pairs" ]; do
    first=$(( seed + 1 + 2 * n )) second=$(( seed + 2 + 2 * n ))
    one parent "$first" 0
    one change "$first" 0
    one change "$second" 0
    one parent "$second" 0
    n=$(( n + 1 ))
done
n=0
while [ "$n" -lt "$more" ]; do
    one change $(( seed + 1 + 2 * pairs + n )) 0
    n=$(( n + 1 ))
done
echo "total $(( $(date +%s) - began )) s"
