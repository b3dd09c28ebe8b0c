#!/bin/bash
# Prove one cell on the chip, in one call (one process per run, so each
# run pays its own set-up, as in a check):
#
#   bash benchmark/tests/prove.sh <workload> <run_seconds> <first seed> \
#       [sets] [trace] [readings] [control]
#
# sets:     two sets of six runs with --trace 0, the same six seeds in each
# trace:    three runs with --trace 1, on three further seeds
# readings: the program on twelve further seeds in one process, with a
#           2-second window (the lower readings of each limit)
# control:  the program's bf16 storage path on three of those seeds (the
#           upper readings)
#
# Result lines go to chiprun_out/prove/<workload>/, one summary line per
# run to standard output.
set -u
W=$1; S=$2; B=$3; shift 3
WHAT=${*:-sets trace readings control}
OUT=chiprun_out/prove/$W
mkdir -p "$OUT"
brief() {
  python3 -c 'import json,sys
for line in sys.stdin:
    line = line.strip()
    if not line.startswith("{"): continue
    d = json.loads(line)
    m = {k: v["value"] for k, v in d["metrics"].items()}
    c = {k: v["value"] for k, v in d["checks"].items()}
    print(d.get("seed", ""), d.get("variant", ""), "correct", d["correct"],
          "attempted", d["attempted"], m, c, "peak", d["device"]["memory_peak_bytes"],
          "busy", d["device"].get("busy_s"), "window", d["device"].get("window_s"))'
}
for what in $WHAT; do
  case $what in
    sets)
      for set in 1 2; do
        for i in 0 1 2 3 4 5; do
          seed=$((B + i))
          python3 benchmark/run.py --workload "$W" --seed $seed --seconds "$S" \
            --trace 0 > "$OUT/set$set.$seed.out" 2> "$OUT/set$set.$seed.err"
          echo "set$set seed $seed rc $? $(brief < "$OUT/set$set.$seed.out")"
        done
      done ;;
    trace)
      for i in 6 7 8; do
        seed=$((B + i))
        python3 benchmark/run.py --workload "$W" --seed $seed --seconds "$S" \
          --trace 1 > "$OUT/trace.$seed.out" 2> "$OUT/trace.$seed.err"
        echo "trace seed $seed rc $? $(brief < "$OUT/trace.$seed.out")"
        tail -1 "$OUT/trace.$seed.out" | cut -c1-1500
      done ;;
    readings)
      python3 benchmark/tests/rehearse.py --workload "$W" --seconds 2 \
        --seed $(seq $((B + 10)) $((B + 21))) > "$OUT/readings.out" \
        2> "$OUT/readings.err"
      echo "readings rc $?"; brief < "$OUT/readings.out" ;;
    control)
      python3 benchmark/tests/rehearse.py --workload "$W" --seconds 2 \
        --dtype bf16 --seed $((B + 10)) $((B + 11)) $((B + 12)) \
        > "$OUT/control.out" 2> "$OUT/control.err"
      echo "control rc $?"; brief < "$OUT/control.out" ;;
  esac
done
