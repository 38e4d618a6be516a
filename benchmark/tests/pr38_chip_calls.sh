#!/usr/bin/env bash
# PR 38, after REVIEW 38: the chip calls of the second session, as they were
# run (the scripts of calls 5-7 were not kept; these re-read what they read).
# Nothing here is run by the benchmark or by a test.
#
# Before a call, in the sandbox, from the root of the repo (all three
# directories are listed in .gitignore and travel with the copy):
#
#   rm -rf .archive_check .parent_check .parent_overlay
#   mkdir -p .archive_check .parent_check .parent_overlay
#   git add -A && git archive "$(git write-tree)" | tar -x -C .archive_check   # the change: what git would commit
#   git archive HEAD | tar -x -C .parent_check                                 # the parent, 6dd8b99
#   git archive HEAD | tar -x -C .parent_overlay                               # the parent under this PR's
#   cp -r BENCHMARK.json benchmark .parent_overlay/                            #   benchmark files (a new cell)
#
#   chiprun --chips 1 --timeout 2400 -- bash benchmark/tests/pr38_chip_calls.sh call8
#   chiprun --chips 1 --timeout 900  -- bash benchmark/tests/pr38_chip_calls.sh call9
#   chiprun --chips 1 --timeout 600  -- bash benchmark/tests/pr38_chip_calls.sh call10
set -u
root=$PWD
out=$root/chiprun_out
mkdir -p "$out"
cell=serve-gdn-moe-sat

# one run in directory $1, its whole output to $out/$2.log, the lines that
# say what it read to the call's own output
one() {
  local dir=$1 log=$out/$2.log t0=$SECONDS
  shift 2
  (cd "$root/$dir" && "$@") > "$log" 2>&1
  local rc=$?  # (call 8 printed basename's status here: every "exit 0" of its output says nothing)
  echo "== $(basename "$log" .log): exit $rc after $((SECONDS - t0)) s"
  grep -aE "check .*(gap|compiles|not_finished)|hand-off:|reference:|window:|setup_s |^\{|Error" "$log" | cut -c1-4000
}

case "${1:-}" in
call8)
  # the traced run first: every per-layer metric the cell lists has to be in
  # its line, paged_attn_device_share.tput (listed since REVIEW 38) among them
  one .archive_check call8_traced python3 benchmark/run.py \
    --workload $cell --seed 3800000800 --seconds 45 --trace 1
  # the state not handed from prefill to decode, at the cell's own size and
  # rate: first_decoded_mean_logit_gap has to fail on each seed
  for seed in 3800000811 3800000812; do
    one .archive_check call8_fault_no_state_handoff_$seed \
      python3 benchmark/tests/qwen3_next_faults.py --fault no_state_handoff \
      --workload $cell --seed $seed --seconds 20 --trace 0
  done
  # six seeds of what git would commit
  for seed in 3800000821 3800000822 3800000823 3800000824 3800000825 3800000826; do
    one .archive_check call8_six_$seed python3 benchmark/run.py \
      --workload $cell --seed $seed --seconds 45 --trace 0
  done
  # the parent under this PR's benchmark files: it has to fail at once
  one .parent_overlay call8_parent_newcell timeout 300 python3 benchmark/run.py \
    --workload $cell --seed 3800000801 --seconds 45 --trace 0
  tail -n 3 "$out/call8_parent_newcell.log" | cut -c1-600
  ;;
call9)
  # every prefill reads its head at one row now: the dense cell whose guarded
  # number holds prefills, parent and change at one seed, then traced
  seed=3800000901
  one .parent_check call9_knee_parent python3 benchmark/run.py \
    --workload serve-prefill-knee --seed $seed --seconds 45 --trace 0
  one .archive_check call9_knee_change python3 benchmark/run.py \
    --workload serve-prefill-knee --seed $seed --seconds 45 --trace 0
  ;;
call10)
  # the same pair the other way round at another seed: the change's prefill
  # programs are in the machine's compile cache now (call 9 compiled them
  # inside its setup_s)
  seed=3800000902
  one .archive_check call10_knee_change python3 benchmark/run.py \
    --workload serve-prefill-knee --seed $seed --seconds 45 --trace 0
  one .parent_check call10_knee_parent python3 benchmark/run.py \
    --workload serve-prefill-knee --seed $seed --seconds 45 --trace 0
  ;;
*)
  echo "usage: $0 call8|call9|call10" >&2
  exit 2
  ;;
esac
