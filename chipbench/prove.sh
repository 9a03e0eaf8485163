#!/bin/bash
# One chip call proves one cell: the first (compiling) run, the two
# sets of six runs, three further seeds, three traced runs and the
# control on three seeds, in one sequence that shares the compile
# cache. Every last line goes to chiprun_out/prove/<cell>/.
#
#   chiprun --chips 1 --timeout 3500 -- bash chipbench/prove.sh <cell> [seconds] [phases]
#
# phases (default "first sets seeds traced control") pick what runs.
set -u
cell="$1"
seconds="${2:-$(python3 -c 'import json;print(json.load(open("BENCHMARK.json"))["run_seconds"])')}"
phases="${3:-first sets seeds traced control}"
out="chiprun_out/prove/$cell"
mkdir -p "$out"
# the driver's seeds are large, a little over 2**31 at the most
SET=(2147483693 1500000001 907654321 2100000011 33550337 1234567891)
MORE=(2147483777 1999999973 1000000007)
TRACED=(2147483659 1800000011 271828183)
CONTROL=(2147483693 1500000001 907654321)

run() { # name seed trace [program]
  local name="$1" seed="$2" trace="$3" prog="${4:-chipbench/run.py}"
  local t0=$SECONDS
  local args=(--workload "$cell" --seed "$seed" --seconds "$seconds" --trace "$trace")
  # the control answers at once: a short window at the cell's own load
  [ "$prog" = "chipbench/control.py" ] && args=(--workload "$cell" --seed "$seed" --seconds 10)
  python3 "$prog" "${args[@]}" > "$out/$name.out" 2> "$out/$name.err"
  local rc=$?
  echo "$name seed=$seed trace=$trace rc=$rc took=$((SECONDS - t0))s $(tail -n 1 "$out/$name.out" | cut -c1-600)"
  tail -n 6 "$out/$name.err" | grep -v -i "hugepage\|warnings.warn" | sed 's/^/    /'
}

for phase in $phases; do
  case "$phase" in
    first) run first 2147483647 0 ;;
    sets) for s in 1 2; do for seed in "${SET[@]}"; do run "set$s.$seed" "$seed" 0; done; done ;;
    seeds) for seed in "${MORE[@]}"; do run "seed.$seed" "$seed" 0; done ;;
    traced) for seed in "${TRACED[@]}"; do run "traced.$seed" "$seed" 1; done ;;
    control) for seed in "${CONTROL[@]}"; do run "control.$seed" "$seed" 0 chipbench/control.py; done ;;
    short) for seed in 2147483647 1500000001 907654321; do run "short.$seed" "$seed" 0; done
           run "short.traced" 2147483659 1 ;;
    keeptrace) run "keeptrace" 2147483659 1
           find chipbench/.trace -name '*.xplane.pb' -exec sh -c 'gzip -c "$1" > "$2"' _ {} "$out/trace.xplane.pb.gz" \; ;;
  esac
done
python3 chipbench/spread.py "$out" || true
