#!/bin/bash
# Parent against change on one chip, in one call, so that both are measured on
# the same machine:
#
#   chiprun --timeout 3500 -- bash perfbench/tools/compare.sh <parent-dir> <tag> \
#       "<side> <workload> <seed> <trace>" ...
#
# <side> is P, the parent's checkout (<parent-dir>: an unpacked `git archive`
# of the parent commit with this tree's BENCHMARK.json and perfbench/ laid over
# it, as the driver lays them), or C, this tree. Both sides keep their compiled
# programs in one directory (JAX_COMPILATION_CACHE_DIR, unless the machine
# comes with one set), so the first run of a side says how much of the other's
# cache it finds again and the later ones are warm. A traced run goes through
# tools/timeline.py, which also writes what the host-lane spans held. With
# COMPARE_DEADLINE_S set, a run is only started while fewer seconds than that
# have passed since the first. Every run's output and errors go to
# chiprun_out/<tag>.<side>.<seed>.t<trace>.{log,err,spans.json}.
set -u
here="$(pwd)"; out="$here/chiprun_out"; mkdir -p "$out"
export JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$here/.jax_cache}"
parent="$1"; tag="$2"; shift 2
begin=$(date +%s)
for spec in "$@"; do
  if [ -n "${COMPARE_DEADLINE_S:-}" ] && [ $(( $(date +%s) - begin )) -gt "$COMPARE_DEADLINE_S" ]; then
    echo "== skipped for time: $spec"; continue
  fi
  set -- $spec
  side="$1"; name="$tag.$1.$3.t$4"
  if [ "$side" = P ]; then cd "$here/$parent" || exit 2; else cd "$here" || exit 2; fi
  cmd=(python3 perfbench/run.py)
  [ "$4" = 1 ] && cmd=(python3 perfbench/tools/timeline.py --spans "$out/$name.spans.json")
  start=$(date +%s)
  "${cmd[@]}" --workload "$2" --seed "$3" --seconds 50 --trace "$4" \
      > "$out/$name.log" 2> "$out/$name.err"
  echo "== $name: exit $? after $(( $(date +%s) - start )) s"
  grep -E '^\[run\] (server ready|step latency|generator lateness|trace:)' "$out/$name.log" | cut -c1-420
  grep -E '^\[timeline\]' "$out/$name.err"
  tail -n 1 "$out/$name.log" | python3 -c '
import json, sys
r = json.loads(sys.stdin.readline())
print("   correct", r["correct"], "failed", r["failed"], "of", r["attempted"],
      {k: round(v["value"], 3) for k, v in r["metrics"].items()},
      "setup_s", round(r["window"]["values"]["setup_s"], 1),
      "tokens_per_s", r["window"]["values"].get("tokens_per_s"),
      "peak", r["device"].get("memory_peak_bytes"), r["device"].get("kind"))
for k, v in (r.get("breakdown") or {}).get("idle_gaps", []):
    print("   idle", k, round(v, 6))
'
done
