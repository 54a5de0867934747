#!/bin/bash
# By hand, on the chip: the measurement a benchmark PR owes for one cell —
# two sets of six timed runs, a seed a run (set 2 continues where set 1's
# seeds end, as the driver runs a PR's two sets), three traced runs on
# further seeds, and EXTRA (default 0) one-pass runs on yet further seeds
# for the limits' lower readings, all in one call from a checkout's root:
#
#   chiprun --chips 1 --timeout 3500 -- bash benchmark/tests/full_sets.sh <workload> <seconds> [first-seed]
#
# In the environment: TRACED=<n> makes n traced runs instead of three;
# SETS="1" makes one set; SAME_SEED=1 gives every timed run the first seed
# (the machine's own noise, beside what the seeds add); OUT=<dir> writes
# elsewhere than chiprun_out/sets (a checkout unpacked under the repo
# gives the repo's, so that the results come back).
#
# Every run's last line goes to $OUT/<workload>.jsonl with the set and
# seed in front; standard error of each run to <workload>.err.
# summarize_sets.py reads the .jsonl.
w=$1; secs=$2; base=${3:-2147483700}
out=${OUT:-chiprun_out/sets}; mkdir -p $out
: > $out/$w.err
run() {  # kind seed trace
  local t0=$SECONDS
  python3 benchmark/run.py --workload $w --seed $2 --seconds $secs --trace $3 > $out/$w.last 2>> $out/$w.err
  local rc=$?
  echo "{\"set\": \"$1\", \"seed\": $2, \"trace\": $3, \"rc\": $rc, \"wall_s\": $((SECONDS - t0)), \"window\": $(grep '"phase": "window"' $out/$w.last || echo null), \"line\": $(tail -n 1 $out/$w.last | grep '^{' || echo null)}" >> $out/$w.jsonl
  grep '"phase": "start"\|"phase": "memory"\|"phase": "engines"\|"phase": "window"\|"phase": "reference"\|"phase": "pass"\|"phase": "read_back"' $out/$w.last | cut -c1-600 >> $out/$w.phases
}
traced=${TRACED:-3}
if [ $traced -ge 1 ]; then run traced $((base + 100)) 1; fi
for s in ${SETS:-1 2}; do for k in 0 1 2 3 4 5; do
  if [ -n "$SAME_SEED" ]; then run set$s $base 0; else run set$s $((base + 6 * (s - 1) + k)) 0; fi
done; done
for t in $(seq 1 $((traced - 1))); do run traced $((base + 100 + t)) 1; done
secs=1
for t in $(seq 1 ${EXTRA:-0}); do run extra $((base + 200 + t)) 0; done
tail -c 3000 $out/$w.err
cut -c1-400 $out/$w.jsonl
