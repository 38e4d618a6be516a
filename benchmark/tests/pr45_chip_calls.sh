#!/usr/bin/env bash
# PR 45: the chip calls as they were run (the PR's first session found no
# machine in 70 asks; its second ran these two). Nothing here is run by the
# benchmark or by a test.
#
# Before every call, in the sandbox, from the root of the repo (the directories are
# listed in .gitignore and travel with the copy):
#
#   rm -rf .archive_check .parent_check .parent_overlay
#   mkdir -p .archive_check .parent_check .parent_overlay
#   git add -A && git archive "$(git write-tree)" | tar -x -C .archive_check   # the change: what git would commit
#   git archive HEAD | tar -x -C .parent_check                                 # the parent, c056b79
#   git archive HEAD | tar -x -C .parent_overlay                               # the parent under this PR's
#   cp -r BENCHMARK.json benchmark .parent_overlay/                            #   benchmark files (a new cell)
#
#   chiprun --chips 1 --timeout 2600 -- bash benchmark/tests/pr45_chip_calls.sh call1
set -u
root=$PWD
out=$root/chiprun_out
mkdir -p "$out"
cell=serve-swa-moe-mixed-sat
faults=no_band,rope_on_full,rope_off_window,router_post_attn,silu_gate,ring_not_written,ring_one_block_short

# one run in directory $1, its whole output to $out/$2.log, the lines that
# say what it read to the call's own output
one() {
  local dir=$1 log=$out/$2.log t0=$SECONDS
  shift 2
  (cd "$root/$dir" && "$@") > "$log" 2>&1
  local rc=$?
  echo "== $(basename "$log" .log): exit $rc after $((SECONDS - t0)) s"
  grep -aE "check .*(gap|compiles|not_finished)|judges nothing|reference:|hand-off:|window:|setup_s |^\{|^SWEEP|^SOUND|^CONTROL|^FAULT|^SUMMARY|CRASHED|Error|roofline|scope_share" "$log" | cut -c1-3000
}

# the rate the cell offers, by the issue's rule, from a saturated 45 s run's
# log: 1.25 x (tokens completed in the window / 45 s / the mix's mean answer
# of 455.72 tokens), to two places
rate_from() {
  python3 - "$1" <<'PY'
import re, sys
log = open(sys.argv[1]).read()
tokens, seconds = re.search(r"(\d+) tokens in ([\d.]+)s", log).groups()
print(f"{1.25 * int(tokens) / float(seconds) / 455.72:.2f}")
PY
}

set_rate() {
  python3 - "$1" <<'PY'
import json, sys
path = ".archive_check/benchmark/workloads/serve-swa-moe-mixed-sat.json"
spec = json.load(open(path)); spec["traffic"]["rate_per_s"] = float(sys.argv[1])
json.dump(spec, open(path, "w"), indent=1)
PY
}

case "${1:-}" in
call1)
  # The first run of the cell on the chip, traced, at the issue's reckoning of
  # 3.8 req/s (saturated whatever the knee, if the reckoning is near), and
  # the scope table of its two programs; stop there if it did not run. The
  # rate by the rule above, written into the copy git would commit. One sound
  # seed and the int8 control at a short window: the two sides of the limits
  # (the CPU at the published widths had read the sound side SIX times the
  # first session's borrowed 0.05, so `correct` is read from the printed
  # checks, not from the flag). The parent tried on the new cell (it must
  # fail at once). Then the copy at the new rate on six seeds, as many as the
  # call's 45 minutes hold, and the sweep if any are left.
  one .archive_check c1_traced python3 benchmark/run.py \
    --workload $cell --seed 4500000101 --seconds 45 --trace 1
  grep -q '"correct"' "$out/c1_traced.log" || { tail -60 "$out/c1_traced.log" | cut -c1-400; exit 1; }
  (cd .archive_check && python3 benchmark/tests/scope_table.py $cell jit__decode jit__prefill) \
    > "$out/c1_scopes.txt" 2>&1; head -90 "$out/c1_scopes.txt" | cut -c1-200
  rate=$(rate_from "$out/c1_traced.log"); echo "== RATE $rate req/s offered from c1_traced"
  set_rate "$rate"
  one .archive_check c1_limits timeout 900 python3 benchmark/tests/limits_smallthinker_on_chip.py \
    --workload $cell --seeds 4500000201 --control 1 \
    --seconds 8 --out "$out/c1_limits.json" --dump "$out/c1_limits.npz"
  one .parent_overlay c1_parent_new_cell timeout 300 python3 benchmark/run.py \
    --workload $cell --seed 4500000331 --seconds 45 --trace 0
  tail -3 "$out/c1_parent_new_cell.log" | cut -c1-600
  echo "== $SECONDS s into the call"
  for seed in 4500000321 4500000322 4500000323 4500000324 4500000325 4500000326; do
    [ $SECONDS -lt 2330 ] || break
    one .archive_check c1_seed_$seed python3 benchmark/run.py \
      --workload $cell --seed $seed --seconds 45 --trace 0
  done
  echo "== $SECONDS s into the call"
  if [ $SECONDS -lt 1900 ]; then
    one .archive_check c1_sweep timeout $((2600 - SECONDS)) python3 benchmark/tests/sweep_smallthinker_on_chip.py \
      --workload $cell --rates ${2:-2.0,3.0} --seconds 30 --seed 4500000111
  fi
  echo "== $SECONDS s into the call"
  ;;
call2)
  # the tree git archive gives at the committed rate and limits: one seed of
  # the new cell (`correct` has to read true now), a second seed's sound run
  # and int8 control at a short window, then the two older cells most at
  # risk, parent beside change
  one .archive_check c2_new_cell python3 benchmark/run.py \
    --workload $cell --seed 4500000501 --seconds 45 --trace 0
  one .archive_check c2_limits timeout 600 python3 benchmark/tests/limits_smallthinker_on_chip.py \
    --workload $cell --seeds 4500000202 --control 1 \
    --seconds 8 --out "$out/c2_limits.json" --dump "$out/c2_limits.npz"
  for other in serve-gdn-moe-sat serve-decode-sat; do
    for side in parent_check archive_check; do
      [ $SECONDS -lt 1150 ] || break
      one .$side c2_${other}_$side python3 benchmark/run.py \
        --workload $other --seed 4500000401 --seconds 45 --trace 0
    done
  done
  echo "== $SECONDS s into the call"
  ;;
*)
  echo "usage: $0 call1 | call2"; exit 2 ;;
esac
