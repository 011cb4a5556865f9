#!/bin/bash
# The proof runs of one chip call, in one place, so that the log says what made
# each table of PERF.md:
#
#   chiprun --timeout 2700 -- bash perfbench/tools/proof.sh <dir> <tag> \
#       "<workload> <seed> <seconds> <trace> [<controls>]" ...
#
# <dir> is the checkout the runs are made in: "." or a directory that holds an
# unpacked `git archive $(git write-tree)` (the first run there compiles, the
# later ones find the cache). With PROOF_DEADLINE_S set, a run is only started
# while fewer seconds than that have passed since the first. Every run's output, errors and dump (request
# times, per-token readings) go to chiprun_out/<tag>.<seed>.{log,err,dump.json};
# perfbench/tools/read_dumps.py reads the dumps afterwards, off the chip.
set -u
out="$(pwd)/chiprun_out"; mkdir -p "$out"
cd "$1" || exit 2; tag="$2"; shift 2
begin=$(date +%s)
for spec in "$@"; do
  if [ -n "${PROOF_DEADLINE_S:-}" ] && [ $(( $(date +%s) - begin )) -gt "$PROOF_DEADLINE_S" ]; then
    echo "== skipped for time: $spec"; continue
  fi
  set -- $spec
  name="$tag.$2"
  extra=(); [ -n "${5:-}" ] && extra=(--controls "$5")
  start=$(date +%s)
  python3 perfbench/run.py --workload "$1" --seed "$2" --seconds "$3" --trace "$4" \
      "${extra[@]}" --dump "$out/$name.dump.json" > "$out/$name.log" 2> "$out/$name.err"
  echo "== $name: exit $? after $(( $(date +%s) - start )) s"
  grep '^\[run\]' "$out/$name.log" | cut -c1-600
  grep '^\[check\]' "$out/$name.err"
done
