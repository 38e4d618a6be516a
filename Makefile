# Test tiers.
#
# The gate is what the driver runs (ROADMAP.md "Tier-1 verify",
# /root/TESTS_LAST_RUN.json): `JAX_PLATFORMS=cpu python -m pytest tests/ -q
# -m 'not slow' -p xdist -n 6 --dist loadfile` — last reading 909 passed in
# 257 s (driver, PR 27's tree). `test` is the same suite in one process,
# slow tier included; `test-fast` the in-process pure-logic majority (model
# math, kernels, interop, collectives, data/optim/checkpoint plumbing),
# skipping the subprocess tier: multi-process launchers, example scripts,
# the dryrun, CLI round-trips.
#
# The tier is an explicit FILE LIST, not `-m "not slow"`: deselecting by
# marker reorders the multiprocess tests next to each other and
# reproducibly hangs the XLA:CPU collective rendezvous on this box
# (observed twice: ~6% CPU, 20 threads in futex wait).
#
# tests/conftest.py joins the persistent XLA compilation cache
# (JAX_COMPILATION_CACHE_DIR, else .jax_compile_cache/ — the one rule in
# compilation/cache.py); `test-cold` switches JAX's cache off when hunting
# compiler-level issues.

PYTEST ?= python -m pytest

FAST_FILES = \
  tests/test_hf_interop.py tests/test_models.py \
  tests/test_flash_attention.py tests/test_generation.py \
  tests/test_operations.py tests/test_quantization.py \
  tests/test_moe.py tests/test_accelerator.py \
  tests/test_optimizer_scheduler.py tests/test_state.py \
  tests/test_data_loader.py tests/test_checkpointing.py \
  tests/test_ring_attention.py tests/test_seq2seq.py \
  tests/test_telemetry.py tests/test_compilation.py \
  tests/test_checkpoint_async.py tests/test_fused_accum.py \
  tests/test_diagnostics.py \
  tests/test_serving.py tests/test_serving_obs.py \
  tests/test_elastic.py tests/test_fused_kernels.py \
  tests/test_slice_mesh.py tests/test_adapters.py \
  tests/test_prefix_cache.py tests/test_speculation.py \
  tests/test_profiling.py tests/test_loadgen.py \
  tests/test_capacity.py tests/test_router.py \
  tests/test_disagg.py tests/test_hlo_audit.py \
  tests/test_layering.py

.PHONY: test test-fast test-cold compile-cache-smoke ckpt-smoke accum-smoke \
  diag-smoke serve-smoke serve-obs-smoke elastic-smoke \
  slice-smoke kernels-smoke lora-smoke prefix-smoke spec-smoke mem-smoke \
  soak-smoke capacity-smoke router-smoke disagg-smoke audit-smoke

test:
	$(PYTEST) tests/ -q

test-fast:
	$(PYTEST) $(FAST_FILES) -q

# cache-disabled full run (compiler-issue hunting)
test-cold:
	JAX_ENABLE_COMPILATION_CACHE=false $(PYTEST) tests/ -q

# tiny end-to-end check of the compilation subsystem: AOT warmup compiles
# the real unified_step with zero first-step retraces, and a persistent
# cache dir round-trips to a recorded hit
compile-cache-smoke:
	$(PYTEST) -q \
	  tests/test_compilation.py::test_warmup_then_first_step_never_retraces \
	  tests/test_compilation.py::test_persistent_cache_round_trip_records_hit

# end-to-end crash-safety check of the async checkpoint subsystem: a short
# train loop saving async every 2 steps is SIGKILLed between a save's
# device->host snapshot and its commit rename; the run directory must hold
# only COMMITTED checkpoints plus the orphaned .tmp, and restore must land
# on the last committed one. The blocked-time acceptance test rides along.
ckpt-smoke:
	$(PYTEST) -q \
	  tests/test_checkpoint_async.py::test_kill_between_snapshot_and_commit_falls_back \
	  tests/test_checkpoint_async.py::test_async_blocked_time_excludes_serialization_and_io

# fused-accumulation acceptance on CPU: the fp32 bitwise parity test
# (fused lax.scan == per-microbatch lax.cond after 3 optimizer steps)
# and one dispatch per optimizer step with zero retraces after warmup
accum-smoke:
	$(PYTEST) -q \
	  tests/test_fused_accum.py::test_fused_parity_fp32_bitwise \
	  tests/test_fused_accum.py::test_fused_zero_retraces_after_warmup

# serving acceptance on CPU: paged-engine greedy decode == the dense
# generate path token-for-token, EOS-freed slots refill mid-flight with
# every request completing and no leaked blocks, and zero decode retraces
# after warmup
serve-smoke:
	$(PYTEST) -q \
	  tests/test_serving.py::test_paged_generate_matches_dense_generate \
	  tests/test_serving.py::test_eos_slot_refill_completes_all_requests \
	  tests/test_serving.py::test_zero_decode_retrace_after_warmup

# serving observability acceptance on CPU: the engine runs under
# synthetic overload (16 requests vs 2 slots, 4-deep bounded queue,
# 50ms queue deadline) with the full plane attached — every request
# finishes or sheds with a terminal span, /metrics serves live gauges
# MID-RUN, the Perfetto trace round-trips, and `accelerate-tpu
# diagnose` names the shed counts and SLO attainment. The queue-bound
# and deadline shedding unit tests ride along as fast preflight.
serve-obs-smoke:
	$(PYTEST) -q \
	  tests/test_serving_obs.py::TestSchedulerShedding \
	  tests/test_serving_obs.py::test_overload_smoke_end_to_end

# elastic acceptance on CPU (<120s): a 4-process run loses rank 2 to an
# injected SIGKILL at step 7, the supervisor declares the death, tears
# down and relaunches 3 survivors, and the reshaped (4 -> 3) restore
# resumes from the committed step-5 checkpoint — finishing with
# bitwise-identical state and a loss curve identical to a clean 3-way
# run resumed from the same checkpoint (slow-marked, so tier 1 skips it)
elastic-smoke:
	JAX_PLATFORMS=cpu $(PYTEST) -q \
	  tests/test_elastic.py::test_elastic_kill_and_reform

# slice-level acceptance (<60s CPU): a 2-slice x 2-proc simulated fleet
# loses ALL of slice 1 to an injected `kill@7:slice=1` mid-run; the
# supervisor must drop the whole slice in ONE generation, re-form the
# survivors as a 1-slice world, and finish bitwise-identical to a clean
# 1-slice run resumed from the same committed checkpoint
slice-smoke:
	JAX_PLATFORMS=cpu $(PYTEST) -q \
	  tests/test_elastic.py::test_slice_kill_and_reform

# step-speed kernel acceptance on CPU (<60s): interpret-mode Pallas
# prologue matches the reference chain (values + grads), the fused adamw
# epilogue is BITWISE against the production optax tail with a traced
# clip scale, and a fused-kernels model takes zero retraces after
# warmup. The kernels themselves compile and run only on the chip:
# `python chip_smoke.py` (kernel phase) is that check.
kernels-smoke:
	$(PYTEST) -q \
	  tests/test_fused_kernels.py::test_prologue_kernel_matches_reference \
	  tests/test_fused_kernels.py::test_epilogue_kernel_bitwise_vs_reference \
	  tests/test_fused_kernels.py::test_zero_retraces_after_warmup_with_fused_kernels

# prefix-caching acceptance on CPU (~30s): two requests sharing a long
# template — the second skips prefill for every shared full block and
# decodes bitwise-equal to a cold-cache control; a divergent third
# request exercises copy-on-write and still matches its control, all
# with zero decode retraces. The tenant-isolation test (tenant A's
# cached prefix must never serve tenant B) rides along as preflight.
prefix-smoke:
	JAX_PLATFORMS=cpu $(PYTEST) -q \
	  tests/test_prefix_cache.py::test_tenant_a_cached_prefix_never_serves_tenant_b \
	  tests/test_prefix_cache.py::test_prefix_smoke_end_to_end

# speculative-decoding acceptance on CPU (~60s): a spec-off /
# SpecConfig(k=0) engine is token-for-token AND key-stream identical to
# a plain engine; a self-consistent draft (upper target layers are exact
# no-ops) accepts 100% of drafts while decoding bitwise-equal to the
# spec-off control; verify compiles ONCE, warm set_speculation() toggles
# add zero retraces, and a speculative write into a shared CACHED block
# copies-on-write first (slow-marked e2e, so it runs here but not in
# tier 1; the retrace-free toggle test rides along as preflight)
spec-smoke:
	JAX_PLATFORMS=cpu $(PYTEST) -q \
	  tests/test_speculation.py::test_verify_traces_once_and_toggle_is_retrace_free \
	  tests/test_speculation.py::test_spec_smoke_end_to_end

# multi-tenant adapter acceptance on CPU (~30s): train a LoRA adapter
# through unified_step (adapter-only carry), commit its checkpoint
# through the atomic protocol, load it into a serving engine next to a
# second adapter, and decode token-for-token equal to a single-tenant
# reference — with the multi-adapter batch parity test as preflight
# (slow-marked e2e, so it runs here but not in tier 1)
lora-smoke:
	JAX_PLATFORMS=cpu $(PYTEST) -q \
	  tests/test_adapters.py::test_multi_adapter_batch_bitwise_matches_single_tenant \
	  tests/test_adapters.py::test_lora_smoke_end_to_end

# memory & attribution acceptance on CPU (~20s): AOT warmup registers the
# real unified_step's compiled program (the ledger sums), the live-buffer
# census attributes the warmed carry to params/opt owners with owners +
# unowned summing to total live bytes, and a synthetic RESOURCE_EXHAUSTED
# in a subprocess leaves a parseable oom-report.json autopsy behind
mem-smoke:
	JAX_PLATFORMS=cpu $(PYTEST) -q \
	  tests/test_profiling.py::test_warmup_registers_program_and_ledger_sums \
	  tests/test_profiling.py::test_census_owner_attribution_on_warmed_step \
	  tests/test_profiling.py::test_oom_autopsy_survives_crashing_subprocess

# capacity acceptance on CPU (~30s): chunked prefill decodes greedy-
# bitwise vs the unchunked engine under a per-step token budget with
# zero decode retraces and SRPT ordering, a mid-prefill stall preempts
# instead of wedging, preempt/swap-out/swap-in round-trips KV blocks
# bitwise through host memory with resumed outputs identical, the pool
# swap-ledger fuzz leaks nothing, and int8 paged KV holds >= 1.8x the
# seats by arithmetic while matching greedy outputs
capacity-smoke:
	JAX_PLATFORMS=cpu $(PYTEST) -q tests/test_capacity.py

# soak & chaos acceptance on CPU (~30s): the whole loadgen unit tier
# (deterministic trace, coordinated-omission guard, chaos handlers, SLO
# window fold, report/diagnose plumbing) plus the slow-marked e2e smoke —
# a seeded ramp->soak->fault->recovery program against a REAL engine on
# the virtual clock, asserting a populated soak-report.json, measured
# recovery, bounded fault damage, zero decode retraces, a reproducible
# trace, and bounded memory in every ring (the e2e runs here, not tier 1)
soak-smoke:
	JAX_PLATFORMS=cpu $(PYTEST) -q tests/test_loadgen.py

# fleet serving acceptance on CPU (~15s): router unit tier on fake
# clocks + engines (least-loaded under skew, prefix-affinity beats
# round-robin on warm hits, session spill on drain, stale snapshots
# never wedge, replica_kill/replica_slow accounting) plus real-engine
# smokes — drain finishes seats while shedding new work, the prefix
# digest is tenant-scoped, and a 3-replica fleet produces identical
# outputs under affinity vs round-robin with strictly more warm hits
router-smoke:
	JAX_PLATFORMS=cpu $(PYTEST) -q tests/test_router.py

# prefill/decode disaggregation acceptance on CPU (~35s): greedy
# outputs across the block-granular KV hand-off are BITWISE the
# colocated engine's (bf16 and int8), the int8 swap payload round-trips
# exactly (scale rows included), manifest seating dedups against the
# decode replica's CACHED index, and the transfer_stall / transfer_drop
# chaos arms bound damage to a re-queue — no request lost, no seated
# decode disturbed, measured recovery
disagg-smoke:
	JAX_PLATFORMS=cpu $(PYTEST) -q tests/test_disagg.py

# sharding X-ray acceptance on CPU (~20s): the paged decode and the
# spec-verify program compile collective-CLEAN under fsdp weight
# sharding on a 4-device CPU mesh (zero involuntary reshards — the
# CPU-feasible half of ROADMAP (a)), with the mis-pinned-sharding
# fixture as preflight proving the detector actually fires
audit-smoke:
	JAX_PLATFORMS=cpu $(PYTEST) -q \
	  tests/test_hlo_audit.py::test_mis_pinned_sharding_trips_violation \
	  tests/test_hlo_audit.py::test_audit_smoke_decode_and_verify_clean_under_fsdp

# diagnostics end-to-end on CPU: a tiny train loop with an injected slow
# step and an injected NaN gradient runs with the flight recorder on,
# anomalies fire (rate-limited), the run dumps, and `accelerate-tpu
# diagnose` turns the directory into a report. The SIGKILL survivability
# test rides along (slow-marked, so it runs here but not in tier 1).
diag-smoke:
	$(PYTEST) -q \
	  tests/test_diagnostics.py::test_accelerator_diagnostics_end_to_end \
	  tests/test_diagnostics.py::test_sigkilled_run_leaves_dump_diagnose_names_it
