#!/usr/bin/env bash
# PR 40: the chip calls as they were run. Nothing here is run by the benchmark
# or by a test.
#
# Before calls 4, 6, 7, 8 and 9, in the sandbox, from the root of the repo (the three
# directories are listed in .gitignore and travel with the copy):
#
#   rm -rf .archive_check .parent_check .parent_overlay
#   mkdir -p .archive_check .parent_check .parent_overlay
#   git add -A && git archive "$(git write-tree)" | tar -x -C .archive_check   # the change: what git would commit
#   git archive HEAD | tar -x -C .parent_check                                 # the parent, 7ba56be
#   git archive HEAD | tar -x -C .parent_overlay                               # the parent under this PR's
#   cp -r BENCHMARK.json benchmark .parent_overlay/                            #   benchmark files (a new cell)
#
#   chiprun --chips 1 --timeout 2700 -- bash benchmark/tests/pr40_chip_calls.sh call1
set -u
root=$PWD
out=$root/chiprun_out
mkdir -p "$out"
cell=serve-mla-moe-longctx-sat
faults=no_k_rope,no_mscale,no_latent_norm,no_group_limit,no_shared_expert,plain_rope

# one run in directory $1, its whole output to $out/$2.log, the lines that
# say what it read to the call's own output
one() {
  local dir=$1 log=$out/$2.log t0=$SECONDS
  shift 2
  (cd "$root/$dir" && "$@") > "$log" 2>&1
  local rc=$?
  echo "== $(basename "$log" .log): exit $rc after $((SECONDS - t0)) s"
  grep -aE "check .*(gap|compiles|not_finished)|judges nothing|reference:|window:|setup_s |^\{|^SWEEP|^SOUND|^CONTROL|^FAULT|^SUMMARY|CRASHED|Error" "$log" | cut -c1-4000
}

case "${1:-}" in
call1)
  # the first run of the cell on the chip, traced (offered 2.0 req/s then),
  # the scope table of its two programs (where a relayout of the pool would
  # show), and the sweep
  one . c1_traced python3 benchmark/run.py \
    --workload $cell --seed 4000000101 --seconds 45 --trace 1
  python3 benchmark/tests/scope_table.py $cell jit__decode jit__prefill \
    > "$out/c1_scopes.txt" 2>&1; head -70 "$out/c1_scopes.txt" | cut -c1-200
  one . c1_sweep python3 benchmark/tests/sweep_deepseek_v3_on_chip.py \
    --workload $cell --rates 1.0,1.5,2.0 --seconds 30 --seed 4000000111
  ;;
call2)
  # what the limits are set from: sound seeds at a short window (the 32
  # seated requests and what arrives in 8 s, drained) and the int8 control
  one . c2_limits python3 benchmark/tests/limits_deepseek_v3_on_chip.py \
    --workload $cell --seeds 4000000201,4000000202,4000000203,4000000204,4000000205,4000000206 \
    --control 2 --seconds 8 --out "$out/c2_limits.json" --dump "$out/c2_limits.npz"
  ;;
call3)
  # the six planted faults, each on one seed of call 2's
  one . c3_faults python3 benchmark/tests/limits_deepseek_v3_on_chip.py \
    --workload $cell --seeds 4000000201 --control 0 --faults $faults \
    --fault-seeds 1 --seconds 8 --out "$out/c3_faults.json" --dump "$out/c3_faults.npz"
  ;;
call4)
  # the first committed tree (the first initialiser, 1.37 req/s): the traced
  # run, six seeds at the cell's own 45 s — they spread 4.7 % — and the
  # parent under this PR's benchmark files, which has to fail at once
  one .archive_check c4_traced python3 benchmark/run.py \
    --workload $cell --seed 4000000400 --seconds 45 --trace 1
  for seed in 4000000421 4000000422 4000000423 4000000424 4000000425 4000000426; do
    one .archive_check c4_six_$seed python3 benchmark/run.py \
      --workload $cell --seed $seed --seconds 45 --trace 0
  done
  one .parent_overlay c4_parent_newcell timeout 300 python3 benchmark/run.py \
    --workload $cell --seed 4000000401 --seconds 45 --trace 0
  tail -n 3 "$out/c4_parent_newcell.log" | cut -c1-600
  ;;
call5)
  # the second initialiser (o_proj a quarter, the selection bias a tenth):
  # what the limits are set from, again — sound seeds, the int8 control on
  # two of them, the six faults on one
  one . c5_limits python3 benchmark/tests/limits_deepseek_v3_on_chip.py \
    --workload $cell --seeds 4000000501,4000000502,4000000503,4000000504,4000000505 \
    --control 2 --faults $faults --fault-seeds 1 --seconds 8 \
    --out "$out/c5_limits.json" --dump "$out/c5_limits.npz"
  ;;
call6)
  # what git would commit: the traced run, six seeds at the cell's own 45 s,
  # the parent under this PR's benchmark files and the smoke run's latent phase
  one .archive_check c6_traced python3 benchmark/run.py \
    --workload $cell --seed 4000000600 --seconds 45 --trace 1
  (cd .archive_check && python3 benchmark/tests/scope_table.py $cell \
    jit__decode jit__prefill) > "$out/c6_scopes.txt" 2>&1
  for seed in 4000000621 4000000622 4000000623 4000000624 4000000625 4000000626; do
    one .archive_check c6_six_$seed python3 benchmark/run.py \
      --workload $cell --seed $seed --seconds 45 --trace 0
  done
  one .parent_overlay c6_parent_newcell timeout 300 python3 benchmark/run.py \
    --workload $cell --seed 4000000601 --seconds 45 --trace 0
  tail -n 3 "$out/c6_parent_newcell.log" | cut -c1-600
  one .archive_check c6_smoke_mla python3 chip_smoke.py --phases mla
  tail -n 12 "$out/c6_smoke_mla.log" | cut -c1-400
  ;;
call7)
  # the cell that shares MoE(decode=True) and the engine's host path with
  # the new one, parent and change at one seed
  w=serve-gdn-moe-sat
  one .parent_check c7_${w}_parent python3 benchmark/run.py --workload $w --seed 4000000701 --seconds 45 --trace 0
  one .archive_check c7_${w}_change python3 benchmark/run.py --workload $w --seed 4000000701 --seconds 45 --trace 0
  ;;
call8)
  # after REVIEW 40: the cell re-rated to 1.51 req/s (1.25 x call 6's 1.205)
  # and the experts' rows cut inside moe_ragged under the one budget — what
  # git would commit: the traced run, six seeds at the cell's own 45 s, and
  # the parent under this PR's benchmark files
  one .archive_check c8_traced python3 benchmark/run.py \
    --workload $cell --seed 4000000800 --seconds 45 --trace 1
  (cd .archive_check && python3 benchmark/tests/scope_table.py $cell \
    jit__decode jit__prefill) > "$out/c8_scopes.txt" 2>&1
  for seed in 4000000821 4000000822 4000000823 4000000824 4000000825 4000000826; do
    one .archive_check c8_six_$seed python3 benchmark/run.py \
      --workload $cell --seed $seed --seconds 45 --trace 0
  done
  one .parent_overlay c8_parent_newcell timeout 300 python3 benchmark/run.py \
    --workload $cell --seed 4000000801 --seconds 45 --trace 0
  tail -n 3 "$out/c8_parent_newcell.log" | cut -c1-600
  ;;
call9)
  # two more traced runs of the same tree at seeds of their own: the spread
  # of decode_step_ms.tput and of the shares beside the guarded number
  for seed in 4000000901 4000000902; do
    one .archive_check c9_traced_$seed python3 benchmark/run.py \
      --workload $cell --seed $seed --seconds 45 --trace 1
  done
  ;;
call10)
  # the smoke run's latent phase on the working tree, after the cut moved
  python3 chip_smoke.py --phases mla
  ;;
*)
  echo "usage: $0 call1|...|call10" >&2
  exit 2
  ;;
esac
