"""The bench variant registry: what can run, in what order, at what cost.

Each :class:`Variant` carries the scheduling metadata the deadline
scheduler needs — ``priority`` (lower runs earlier; the headline
``dense`` is 0 and always first), ``group`` (variants sharing a model
config run in ONE child process, cutting the serial process-spawn +
recompile tax that ate r05), ``fast`` (membership in the CI ``--fast``
subset), and ``default_estimate_s`` (the cost guess used until a
measured estimate is persisted next to the XLA cache).

Within a group the registration order is the run order, chosen so an
expected-informative failure (``longseq_xla`` OOMing on 16G) is LAST and
cannot take down a measurable sibling.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Optional

ENV_ITERS = "ACCELERATE_TPU_BENCH_ITERS"  # test/debug: stretch train loops


@dataclass(frozen=True)
class Variant:
    name: str
    kind: str  # "train" | "ckpt" | "accum" | "decode" | "decode_load" | "serve" | "serve_soak" | "fleet_soak" | "disagg_soak" | "overhead" | "lora"
    priority: int
    group: str
    args: tuple = field(default_factory=tuple)
    fast: bool = False
    headline: bool = False
    default_estimate_s: float = 600.0
    expected_oom: bool = False  # failure is itself the informative outcome


class VariantRegistry:
    def __init__(self, variants: list[Variant]):
        self._variants = {v.name: v for v in variants}
        self._order = [v.name for v in variants]

    def __contains__(self, name: str) -> bool:
        return name in self._variants

    @property
    def names(self) -> list[str]:
        return list(self._order)

    def get(self, name: str) -> Variant:
        return self._variants[name]

    @property
    def headline(self) -> Optional[str]:
        for name in self._order:
            if self._variants[name].headline:
                return name
        return None

    def select(self, names: Optional[list[str]] = None,
               fast: bool = False) -> "VariantRegistry":
        if names is not None:
            unknown = [n for n in names if n not in self._variants]
            if unknown:
                raise KeyError(
                    f"unknown bench variant(s) {unknown}; "
                    f"choose from {sorted(self._variants)}"
                )
            return VariantRegistry(
                [self._variants[n] for n in self._order if n in set(names)]
            )
        if fast:
            return VariantRegistry(
                [self._variants[n] for n in self._order
                 if self._variants[n].fast]
            )
        return self

    def groups(self) -> list[tuple[str, list[Variant]]]:
        """Process groups ordered by (best member priority, registration
        order); member order inside a group is registration order."""
        by_group: dict[str, list[Variant]] = {}
        first_seen: dict[str, int] = {}
        for i, name in enumerate(self._order):
            v = self._variants[name]
            by_group.setdefault(v.group, []).append(v)
            first_seen.setdefault(v.group, i)
        return sorted(
            by_group.items(),
            key=lambda kv: (
                min(v.priority for v in kv[1]), first_seen[kv[0]],
            ),
        )


def _iters_override(iters: int, kind: str) -> int:
    """Test/debug hook: ACCELERATE_TPU_BENCH_ITERS stretches the measured
    loop of train variants (the SIGKILL partial-recovery test needs a
    child that is reliably mid-measurement when killed)."""
    if kind != "train":
        return iters
    env = os.environ.get(ENV_ITERS)
    return int(env) if env else iters


def _variant(name, kind, priority, group, args, **kw) -> Variant:
    cfg, batch, seq, iters, warmup = args[:5]
    rest = args[5:]
    return Variant(
        name=name, kind=kind, priority=priority, group=group,
        args=(cfg, batch, seq, _iters_override(iters, kind), warmup, *rest),
        **kw,
    )


def build_registry(on_tpu: bool) -> VariantRegistry:
    from accelerate_tpu.models import TransformerConfig

    if not on_tpu:  # CI/CPU smoke: tiny shapes, same code paths
        # default estimates are deliberately tight (tiny configs compile
        # + run in seconds): a 120s --fast deadline must PLAN the whole
        # subset, not starve the tail on guesses
        tiny = TransformerConfig.tiny()
        return VariantRegistry([
            # accum registers FIRST inside the shared child: the round's
            # first-run variant eats every cold persistent-cache compile
            # (BENCH_r06: dense 61 misses / 10 hits vs 70-72 hits on every
            # later variant — the headline was paying the whole round's
            # cold-start bill as its own compile badput). dense keeps
            # priority 0 + headline, so the group still schedules first
            # and the consolidated block still leads with it; only the
            # in-child run order moves the cold misses onto accum.
            _variant("accum", "accum", 1, "dense",
                     (tiny, 4, 64, 6, 2), fast=True, default_estimate_s=12),
            # no fused A/B axis here (the TPU registry has it): the Pallas
            # kernels do not run off-chip outside the tests' interpreter
            # context, and an interpreter timing would mean nothing
            _variant("dense", "train", 0, "dense",
                     (tiny, 4, 128, 3, 1, "adamw", False),
                     fast=True, headline=True, default_estimate_s=30),
            _variant(
                "moe", "train", 2, "moe",
                (TransformerConfig.tiny(num_experts=4, num_experts_per_tok=2),
                 4, 128, 3, 1),
                default_estimate_s=20,
            ),
            # B=8 S=256 keeps CPU steps ~0.3s: big enough that the per-
            # step telemetry cost (fixed, host-side) measures well under
            # the 2% bar instead of being amplified by a tiny step
            _variant("overhead", "overhead", 2, "overhead",
                     (tiny, 8, 256, 20, 3), fast=True, default_estimate_s=30),
            # continuous-batched paged decode vs sequential fixed-batch
            # generate; NOT in --fast (it compiles every prefill bucket
            # plus two decode paths — too heavy for the 120s deadline).
            # args: (cfg, max_slots, block_size, n_requests, seed)
            # estimate covers the headline engine+baseline passes, the
            # observability-overhead A/B rounds (4 extra trace replays
            # on the warm engine), the prefix-caching cold/warm A/B on
            # the templated cohort (2 warmup + 2 timed passes), and the
            # speculation A/B (3 arms, each a fresh engine compiling its
            # own program set plus a warmup + timed drain)
            _variant("serve", "serve", 3, "serve", (tiny, 4, 8, 16, 0),
                     default_estimate_s=240),
            # soak & chaos: the loadgen harness drives the same tiny
            # serving config through warmup->ramp->soak->fault->recovery
            # on the wall clock (open-loop arrivals, stall_decode fault
            # mid-soak). Rates self-calibrate from a closed-loop probe,
            # so the ~10-25s program cost is host-independent; NOT fast
            # because the wall-clock phases cannot be shrunk below the
            # SLO windows. After the main program, six short A/B arms
            # (chunked prefill, preemption-vs-shed under pool_pressure,
            # fp-vs-int8 KV) each pay a fresh engine compile — the
            # estimate covers them. args: (cfg, max_slots, block_size,
            # target_requests, seed)
            _variant("serve_soak", "serve_soak", 4, "serve",
                     (tiny, 4, 8, 96, 0), default_estimate_s=240),
            # fleet serving: FOUR in-process replicas behind the router,
            # all on ONE virtual clock (step_dt_s), so the whole
            # multi-replica program is host-speed-independent. Three
            # policy arms (round_robin / least_loaded / prefix_affinity)
            # replay the SAME templated-cohort trace, plus a
            # replica_kill chaos arm measuring re-route damage and
            # time-to-recover. args: (cfg, max_slots_per_replica,
            # block_size, target_requests_per_arm, seed)
            _variant("fleet_soak", "fleet_soak", 5, "serve",
                     (tiny, 2, 8, 64, 0), default_estimate_s=180),
            # prefill/decode disaggregation A/B: 2 prefill + 2 decode
            # replicas hand off KV chains through the router's transfer
            # ledger vs 4 colocated replicas on the SAME bursty
            # long-prompt trace, plus a transfer_stall chaos arm.
            # args mirror fleet_soak's
            _variant("disagg_soak", "disagg_soak", 5, "serve",
                     (tiny, 2, 8, 48, 0), default_estimate_s=240),
            _variant("ckpt", "ckpt", 3, "ckpt", (tiny, 4, 64, 8, 2),
                     fast=True, default_estimate_s=15),
            # adapter-only vs full fine-tune economics + the multi-tenant
            # zero-retrace serving check; shares the dense group's tiny
            # config so it rides the same warm compile cache
            _variant("lora", "lora", 2, "lora", (tiny, 4, 64, 3, 1),
                     fast=True, default_estimate_s=40),
        ])

    import dataclasses

    dense = TransformerConfig(
        # ~916M params (Llama-8B width, depth cut to fit one 16G v5e chip
        # with fp32 master + AdamW state). remat="dots" saves matmul
        # outputs so backward recomputes only elementwise ops — measured
        # ~11% faster than remat="full" at this size.
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=3, num_heads=32, num_kv_heads=8, max_seq_len=1024,
        dtype="bfloat16", remat="dots",
    )
    moe = TransformerConfig(
        # Mixtral-family slice (BASELINE.md supporting config): 8 experts,
        # top-2, MIXTRAL-WIDTH experts (h=4096 — expert matmul width is
        # what drives MXU efficiency), depth cut to fit fp32 master +
        # AdamW on one 16G v5e chip. Round-4 single-chip sweep (20 iters,
        # B=16, S=1024, tokens/s/chip -> MFU):
        #   h=1024 L=4 capacity/dots   74.1k  0.311   (round-3 config)
        #   h=1024 L=4 ragged/dots_rg  74.5k  0.312
        #   h=2048 L=2 capacity/dots   53.5k  0.380
        #   h=4096 L=1 capacity/dots   58.7k  0.475
        #   h=4096 L=1 capacity/none   60.7k  0.490
        #   h=4096 L=1 ragged/dots_rg  62.9k  0.509
        #   h=4096 L=1 ragged/none     63.8k  0.516   <- this config
        # ragged (exact, no capacity padding or drops) beats capacity-1.25
        # at every width once remat stops recomputing ragged_dot; at L=1
        # no remat is needed at all.
        #
        # r5 structural bound for the residual vs the 0.60 bar (xplane
        # trace of 3 steps on v5e + ablations, all at this exact shape):
        #   per-step device time: 29.2% lm_head matmuls (49.4% of counted
        #   FLOPs — ~0.88 MFU-equiv), 26.7% expert ragged_dots (33.2% of
        #   FLOPs — ~0.64), 14.3% attention path (1.6% of FLOPs; shared
        #   with every other line), ~10.5% moe dispatch machinery
        #   (scatter-add combine ~5.5%, routed gathers ~2.1%, router +
        #   combine-weight math ~2.9%, the argsort itself ~0%), ~9%
        #   AdamW update + bf16-cast traffic on the FULL 8-expert stacks
        #   (all experts train, only K=2 compute — MFU's active-FLOPs
        #   accounting correctly charges this as overhead), 3.5% loss
        #   log_softmax over the f32 (16,1023,32000) logits.
        # Ablations: a dense MLP with IDENTICAL active matmul FLOPs
        # (f=7168, no routing) measures 81.8k tok/s = 0.661 MFU — the
        # no-dispatch skeleton ceiling; 0.518 = 0.661 x (200.2/254.3 ms).
        # Combine alternatives measured: inverse-permutation gather+sum
        # is 2.7% SLOWER than the scatter-add (261.3 vs 254.3 ms);
        # folding combine weights into the w_down ragged_dot input is
        # noise (+0.4%). Even with dispatch entirely free, the
        # all-expert AdamW/cast traffic (~23 ms) exceeds the 19.3 ms
        # gap to 0.60 — the shape's ceiling under AdamW is ~0.59, so
        # 0.52 stands as measured, bounded, and attributed rather than
        # unexplained.
        vocab_size=32000, hidden_size=4096, intermediate_size=3584,
        num_layers=1, num_heads=32, num_kv_heads=8, max_seq_len=1024,
        num_experts=8, num_experts_per_tok=2, moe_dispatch="ragged",
        moe_capacity_factor=1.25, dtype="bfloat16", remat=None,
    )
    longseq = TransformerConfig(
        # the long-context regime (VERDICT r2 #10: the S=8k single-chip
        # flash point): S^2 score tensors never materialize. Round-4
        # remat sweep at this shape (B=1, adamw, MFU):
        #   L=3 remat="full"       0.475   (round-3 config; 0.63 dense
        #       ceiling x 6/8 full-recompute bound = 0.47 — the number
        #       is exactly the remat tax, not kernel inefficiency)
        #   L=3 remat="save_attn"  0.474   (kernel fwd recompute is tiny)
        #   L=3 remat="dots"       OOM     (saves every matmul output)
        #   L=3 remat="save_mlp"   OOM by 1.0G (AdamW state crowds it out)
        #   L=2 remat="full"       0.473
        #   L=2 remat="save_mlp"   0.505   <- this config (keeps f-wide
        #       MLP activations; backward recomputes only the attn path)
        # Residual gap to 0.60 is structural at B=1/S=8192: ~11% of
        # counted FLOPs are attention (flash bwd runs below dense-matmul
        # MXU efficiency) plus the remaining attn-path recompute.
        # r5: the one lever the accounting pointed at — a fused
        # single-pass flash backward (5 matmuls/pair vs two-pass's 7) —
        # was built and MEASURED at this shape: 8,137 ms/step vs the
        # two-pass 310/312 ms (chip re-verified healthy between runs).
        # TPU Pallas's consecutive-output-visit rule forces the fused
        # form through a collapsing index map + full-sequence VMEM
        # scratch that defeats Mosaic pipelining (and 1024-blocks
        # overflow the 16 MiB scoped vmem). The two-pass backward is
        # the structural optimum here — see ops/flash_attention.py's
        # FUSED_BWD block for the full record.
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=2, num_heads=32, num_kv_heads=8, max_seq_len=8192,
        dtype="bfloat16", remat="save_mlp", attention_impl="flash",
    )
    decode = TransformerConfig(
        # GPT-J-6B-class decoder (~5.5B params, bf16-resident ~11G on the
        # 16G chip) for the reference's HEADLINE metric: big-model
        # generation s/token (benchmarks/README.md:31 — GPT-J-6B fp16 at
        # 0.05 s/token on 2x Titan RTX)
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=24, num_heads=32, num_kv_heads=8, max_seq_len=512,
        dtype="bfloat16",
    )
    small = TransformerConfig(
        # modest width for the accum/ckpt mechanism variants: their
        # metrics (dispatch count, blocked seconds) only need enough
        # compute that the measured overhead is unmistakable next to it
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_layers=2, num_heads=16, num_kv_heads=8, max_seq_len=512,
        dtype="bfloat16",
    )
    return VariantRegistry([
        # headline FIRST on the fresh chip; the runner re-prints the
        # consolidated block with dense LAST for the parse-the-last-line
        # driver. accum shares the dense child: one spawn, one jax init.
        # trailing True = fused A/B axis (unfused + fused_kernels passes
        # in one variant — the estimate covers both compiles + loops)
        _variant("dense", "train", 0, "dense",
                 (dense, 8, 1024, 20, 3, "adamw", True),
                 fast=True, headline=True, default_estimate_s=900),
        _variant("accum", "accum", 1, "dense", (small, 4, 512, 8, 2),
                 fast=True, default_estimate_s=500),
        _variant("decode", "decode", 2, "decode", (decode, 1, 128, 64, 1),
                 default_estimate_s=600),  # B, prompt, new_tokens, reps
        # serving line on the same ~5.5B decode model (shares its child
        # process and resident weights-compile budget); args:
        # (cfg, max_slots, block_size, n_requests, seed)
        _variant("serve", "serve", 3, "decode", (decode, 4, 16, 8, 0),
                 default_estimate_s=2000),
        # soak & chaos on the ~5.5B decode model (same child process /
        # resident compile budget); args mirror serve's. The capacity
        # A/B arms (chunked/preempt/int8) add six engine compiles at
        # this size — the estimate covers them.
        _variant("serve_soak", "serve_soak", 4, "decode",
                 (decode, 4, 16, 64, 0), default_estimate_s=1200),
        # fleet serving on the ~5.5B decode model: 4 in-process replicas
        # per arm share the child's resident-weights budget — each arm
        # compiles its replicas' programs once (virtual clock hides the
        # pauses); 4 arms x 4 replicas drive the estimate
        _variant("fleet_soak", "fleet_soak", 5, "decode",
                 (decode, 2, 16, 48, 0), default_estimate_s=1600),
        # disaggregated prefill/decode on the ~5.5B decode model:
        # 3 arms x 4 replicas (2P+2D or 4 colocated) plus the bitwise
        # hand-off probe — the block transfers ride the PR 17 swap
        # programs already in each replica's compile budget
        _variant("disagg_soak", "disagg_soak", 5, "decode",
                 (decode, 2, 16, 32, 0), default_estimate_s=1600),
        _variant("moe", "train", 3, "moe", (moe, 16, 1024, 20, 3),
                 default_estimate_s=600),
        _variant("longseq", "train", 3, "longseq", (longseq, 1, 8192, 8, 2),
                 default_estimate_s=600),
        # S=4096 comparison pair, where the dense-attention path FITS 16G:
        # guarantees a non-null flash_speedup_vs_xla even when the S=8192
        # xla point OOMs/fails (it was null in rounds 2 and 3). Both run
        # under SGD: with AdamW the ~916M model carries ~11G of fp32
        # master+m+v state and the xla side's fp32 S^2 score tensors push
        # past 16G (measured: 18.26G at S=4096) — the flash/xla RATIO is
        # what this pair exists for, and it is optimizer-invariant as
        # long as both sides match. remat="full" on BOTH sides isolates
        # the kernel delta (measured ~1.5x; under "save_mlp" the saved
        # f-wide buffers perturb the flash side's fusion and the ratio
        # drops to 1.14x while measuring remat interplay, not the kernel).
        _variant(
            "longseq4k", "train", 4, "longseq",
            (dataclasses.replace(longseq, max_seq_len=4096, remat="full"),
             1, 4096, 8, 2, "sgd"),
            default_estimate_s=400,
        ),
        # telemetry+diagnostics ON-vs-OFF A/B: the harness proving itself
        # cheap every round (harness_overhead_pct rides the artifact)
        _variant("overhead", "overhead", 4, "overhead",
                 (TransformerConfig.tiny(), 8, 256, 30, 3),
                 fast=True, default_estimate_s=240),
        # the xla pair is its own group: the S=8192 point is EXPECTED to
        # OOM on 16G chips (itself the flash story), so it runs last in
        # the group where a crash cannot cost the measurable 4k point
        _variant(
            "longseq_xla4k", "train", 5, "longseq_xla",
            (dataclasses.replace(
                longseq, max_seq_len=4096, attention_impl="xla",
                remat="full"),
             1, 4096, 8, 2, "sgd"),
            default_estimate_s=400,
        ),
        _variant(
            "longseq_xla", "train", 6, "longseq_xla",
            (dataclasses.replace(longseq, attention_impl="xla"), 1, 8192, 4, 2),
            default_estimate_s=400, expected_oom=True,
        ),
        # fp8 projections (e4m3 fwd / e5m2 bwd, ops/fp8.py) on the dense
        # headline shape: tokens/s with the matmuls quantized vs the bf16
        # dense line above. TPU-only (CPU has no fp8 MXU paths worth
        # timing) and not in --fast.
        _variant("fp8", "train", 6, "fp8",
                 (dataclasses.replace(dense, fp8=True), 8, 1024, 20, 3),
                 default_estimate_s=600),
        # checkpoint-open -> device-resident for the decode model; its own
        # group so a slow/failed load can never cost the decode headline
        _variant("decode_load", "decode_load", 7, "decode_load",
                 (decode, 1, 0, 0, 0), default_estimate_s=1200),
        # LAST so its disk IO (a ~1 GiB carry written 4x per mode) can
        # never perturb the throughput headlines
        _variant("ckpt", "ckpt", 8, "ckpt", (small, 8, 512, 16, 3),
                 fast=True, default_estimate_s=600),
        # adapter-only vs full fine-tune on the small shape + the
        # multi-tenant zero-retrace serving check; its own group (the
        # serving phase's engine compiles must not warm-start a
        # throughput sibling's cache accounting)
        _variant("lora", "lora", 8, "lora", (small, 4, 512, 8, 2),
                 default_estimate_s=600),
    ])
