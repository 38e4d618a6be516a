"""Sharding X-ray tests: HLO collective parsing, ring bytes estimates,
contract derivation (NO_SHARD vs ZeRO-2 vs hierarchical multi-slice),
the mis-pinned-sharding violation path end to end, KV-gather bytes
sanity vs analytic sizes, and the ROADMAP (a) execution: every captured
serving program (decode, >= 2 prefill buckets, >= 1 verify width, COW)
audited on a 4-device CPU mesh under both ``fsdp`` and ``tensor``
weight layouts with zero involuntary reshards asserted.

All CPU-runnable on the virtual 8-device backend the conftest forces.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from accelerate_tpu.parallel.sharding import (
    collective_contract_for_params,
    collective_contract_for_train,
    mesh_axes_of_params,
)
from accelerate_tpu.profiling import (
    CONTRACT_ZERO,
    ProgramRegistry,
    audit_compiled,
    parse_hlo_collectives,
    parse_replica_groups,
    summarize_audits,
)
from accelerate_tpu.profiling.hlo_audit import (
    RESHARD_COPY,
    estimate_bytes_moved,
)
from accelerate_tpu.utils.dataclasses import ParallelismPlugin, ShardingStrategy


# ---------------------------------------------------------------------- #
# parsing units: both replica_groups formats XLA prints
# ---------------------------------------------------------------------- #
def test_parse_replica_groups_literal_and_iota():
    # literal braces (all-reduce / reduce-scatter print this)
    assert parse_replica_groups("replica_groups={{0,1,2,3},{4,5,6,7}}") == [
        [0, 1, 2, 3], [4, 5, 6, 7],
    ]
    # iota shorthand (all-gather prints this)
    assert parse_replica_groups("replica_groups=[2,4]<=[8]") == [
        [0, 1, 2, 3], [4, 5, 6, 7],
    ]
    # iota with a transpose: groups stride across the device order
    assert parse_replica_groups("replica_groups=[4,2]<=[2,4]T(1,0)") == [
        [0, 4], [1, 5], [2, 6], [3, 7],
    ]
    assert parse_replica_groups("no groups here") is None


def test_parse_hlo_collectives_counts_and_skips_done_halves():
    text = """
  %ag = f32[8,16]{1,0} all-gather(f32[2,16]{1,0} %p0), channel_id=1, replica_groups=[2,4]<=[8], dimensions={0}, use_global_device_ids=true
  %ar-start = f32[4]{0} all-reduce-start(f32[4]{0} %p1), channel_id=2, replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
  %ar-done = f32[4]{0} all-reduce-done(f32[4]{0} %ar-start)
"""
    ops = parse_hlo_collectives(text, num_devices=8, num_slices=1)
    assert [op.kind for op in ops] == ["all-gather", "all-reduce"]
    assert ops[0].group_size == 4
    assert ops[1].group_size == 8
    assert ops[1].is_async
    # async pairs fold into ONE op: the -done half is not double-counted
    assert len(ops) == 2


def test_parse_hlo_collectives_sizes_name_only_operands():
    # jax 0.9's HLO text prints operands as bare names, no inline shape
    # (verbatim from hierarchical_psum on a 2x4 mesh): the operand's
    # size is its definition's result. Parsed as operand_bytes=0 before.
    text = """
  %param.1 = f32[4,3]{1,0} parameter(0), metadata={op_name="x"}
  %reduce_scatter.7 = f32[1,3]{1,0} reduce-scatter(%param.1), channel_id=1, replica_groups={{0,1,2,3},{4,5,6,7}}, use_global_device_ids=true, dimensions={0}, to_apply=%region_0.0, metadata={op_name="jit(hierarchical_psum)/shard_map/reduce_scatter" stack_frame_id=2}
  %psum.7 = f32[1,3]{1,0} all-reduce(%reduce_scatter.7), channel_id=1, replica_groups={{0,4},{1,5},{2,6},{3,7}}, use_global_device_ids=true, to_apply=%region_1.0
  %pair = (f32[2]{0}, s32[]) tuple(%a, %b)
  ROOT %ar2 = (f32[2]{0}, s32[]) all-reduce(%pair), replica_groups={{0,1}}, to_apply=%add
"""
    rs, ar, ar2 = parse_hlo_collectives(text, num_devices=8, num_slices=2)
    assert (rs.kind, rs.operand_bytes, rs.result_bytes) == (
        "reduce-scatter", 48, 12,
    )
    assert rs.bytes_moved == 36  # 48 * 3/4 around the 4-ring
    assert (ar.operand_bytes, ar.bytes_moved, ar.fabric) == (12, 12, "dcn")
    assert ar2.operand_bytes == 12  # tuple-typed definition: 2*4 + 4


def test_parse_hlo_collectives_reads_tpu_reduce_scatter_fusion():
    # XLA:TPU's optimized HLO has no reduce-scatter instruction: a fusion
    # computation named all-reduce-scatter* holds all-reduce + dynamic-slice
    # (verbatim from a ZeRO-3 train step compiled for a v5e 2x2)
    text = """
%all-reduce-scatter.1.clone.clone (input.21: bf16[4096,4096]) -> bf16[4096,1024] {
  %input.21 = bf16[4096,4096]{1,0:T(8,128)(2,1)} parameter(0)
  %all-reduce.86 = bf16[4096,4096]{1,0:T(8,128)(2,1)} all-reduce(%input.21), channel_id=109, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%add.3.clone
  ROOT %dynamic-slice.329 = bf16[4096,1024]{1,0:T(8,128)(2,1)} dynamic-slice(%all-reduce.86, %constant.1476, %multiply.176), dynamic_slice_sizes={4096,1024}
}

ENTRY %main.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %all-reduce.1 = f32[4]{0} all-reduce(%p), replica_groups={{0,1,2,3}}, to_apply=%add
}
"""
    rs, ar = parse_hlo_collectives(text, num_devices=4)
    assert (rs.kind, rs.operand_bytes) == ("reduce-scatter", 4096 * 4096 * 2)
    assert rs.bytes_moved == 4096 * 4096 * 2 * 3 // 4
    assert ar.kind == "all-reduce"  # a plain one outside the fusion stays


def test_ring_bytes_estimates_are_analytic():
    # ring schedules: all-gather moves result*(g-1)/g, reduce-scatter
    # operand*(g-1)/g, all-reduce 2*operand*(g-1)/g
    assert estimate_bytes_moved("all-gather", 0, 1024, 4) == 768
    assert estimate_bytes_moved("reduce-scatter", 1024, 0, 4) == 768
    assert estimate_bytes_moved("all-reduce", 1024, 1024, 4) == 1536
    assert estimate_bytes_moved("collective-permute", 512, 512, 2) == 512
    # degenerate single-member group moves nothing
    assert estimate_bytes_moved("all-gather", 0, 1024, 1) == 0


# ---------------------------------------------------------------------- #
# contract derivation: NO_SHARD vs ZeRO-2 vs hierarchical multi-slice
# ---------------------------------------------------------------------- #
def test_contract_no_shard_is_all_reduce_only():
    plugin = ParallelismPlugin(
        dp_size=8, fsdp_size=1, sharding_strategy=ShardingStrategy.NO_SHARD,
    )
    c = collective_contract_for_train(plugin, mesh=None)
    assert c.permits("all-reduce")
    assert not c.permits("reduce-scatter")
    assert not c.permits("all-gather")
    assert not c.permits("all-to-all")


def test_contract_zero2_allows_scatter_and_gather():
    plugin = ParallelismPlugin(
        dp_size=2, fsdp_size=4,
        sharding_strategy=ShardingStrategy.SHARD_GRAD_OP,
    )
    c = collective_contract_for_train(plugin, mesh=None)
    assert c.permits("reduce-scatter")
    assert c.permits("all-gather")
    assert c.permits("all-reduce")
    assert c.permits(RESHARD_COPY)  # shard_map bodies cross the boundary
    assert not c.permits("all-to-all")


def test_contract_hierarchical_multislice(monkeypatch):
    # > 1 slice: the hierarchical scatter -> cross-slice reduce ->
    # gather path is expected regardless of the sharding strategy
    from accelerate_tpu.parallel.mesh import NUM_SLICES_ENV, build_mesh

    monkeypatch.setenv(NUM_SLICES_ENV, "2")
    mesh = build_mesh(
        ParallelismPlugin(
            dp_size=2, fsdp_size=4,
            sharding_strategy=ShardingStrategy.NO_SHARD,
            min_weight_size=1,
        )
    )
    c = collective_contract_for_train(
        ParallelismPlugin(sharding_strategy=ShardingStrategy.NO_SHARD),
        mesh,
    )
    assert c.permits("reduce-scatter")
    assert c.permits("all-gather")
    assert c.permits("all-reduce")
    assert "slices=2" in c.origin


def test_params_contract_replicated_is_zero():
    params = {"w": jnp.ones((4, 4))}
    assert mesh_axes_of_params(params) == set()
    c = collective_contract_for_params(params)
    assert c.allowed == frozenset()
    assert c.origin == "serve:replicated"


def _mesh(axis: str, n: int = 4) -> Mesh:
    return Mesh(np.array(jax.devices()[:n]), (axis,))


def test_params_contract_follows_actual_leaf_sharding():
    mesh = _mesh("fsdp")
    w = jax.device_put(
        jnp.ones((8, 16)), NamedSharding(mesh, P("fsdp", None)),
    )
    params = {"w": w, "b": jnp.ones((16,))}
    assert mesh_axes_of_params(params) == {"fsdp"}
    c = collective_contract_for_params(params)
    assert c.permits("all-gather")
    assert c.permits("all-reduce")
    assert not c.permits("all-to-all")
    assert not c.permits("collective-permute")


# ---------------------------------------------------------------------- #
# the mis-pinned sharding fixture: provably trips sharding_violation
# ---------------------------------------------------------------------- #
def _mis_pinned_compiled(mesh):
    """A program whose sharding is mis-pinned: an fsdp-sharded weight is
    constrained replicated mid-computation, forcing the compiler to emit
    an involuntary all-gather on what should be a collective-free op."""
    sharded = NamedSharding(mesh, P("fsdp", None))
    replicated = NamedSharding(mesh, P())

    def f(w):
        return jax.lax.with_sharding_constraint(w * 2.0, replicated)

    spec = jax.ShapeDtypeStruct((8, 16), jnp.float32, sharding=sharded)
    return jax.jit(f).lower(spec).compile()


def test_mis_pinned_sharding_trips_violation():
    mesh = _mesh("fsdp")
    audit = audit_compiled(
        "mis_pinned", _mis_pinned_compiled(mesh), contract=CONTRACT_ZERO,
    )
    assert audit.by_kind == {"all-gather": 1}
    assert not audit.clean
    assert len(audit.violations) == 1
    v = audit.violations[0]
    assert v["op_kind"] == "all-gather"
    assert v["op"]  # the offending HLO op is named
    assert v["fabric"] == "ici"
    # exact ring estimate: result is 8*16*4 = 512B, gathered over g=4
    assert v["bytes_moved"] == 512 * 3 // 4


def test_violation_routes_to_sharding_violation_anomaly():
    from accelerate_tpu.diagnostics.anomaly import AnomalyDetector
    from accelerate_tpu.diagnostics.config import DiagnosticsConfig

    mesh = _mesh("fsdp")
    audit = audit_compiled(
        "mis_pinned", _mis_pinned_compiled(mesh), contract=CONTRACT_ZERO,
    )
    det = AnomalyDetector(DiagnosticsConfig())
    out = det.observe_audit(audit.to_record())
    assert len(out) == 1
    anom = out[0]
    assert anom["anomaly_type"] == "sharding_violation"
    assert anom["program"] == "mis_pinned"
    assert anom["op_kind"] == "all-gather"
    assert anom["op"] in anom["ops"]
    # the full audit record travels with the alarm
    assert anom["record"]["violations"] == audit.violations
    # clean audits never fire
    clean = audit_compiled(
        "clean", _mis_pinned_compiled(mesh),
        contract=collective_contract_for_params(
            {"w": jax.device_put(
                jnp.ones((8, 16)), NamedSharding(mesh, P("fsdp", None)),
            )},
        ),
    )
    assert clean.clean
    assert det.observe_audit(clean.to_record()) == []


# ---------------------------------------------------------------------- #
# bytes-estimate sanity vs analytic KV-gather sizes
# ---------------------------------------------------------------------- #
def test_kv_gather_bytes_match_analytic():
    # a KV-pool-shaped tensor (blocks, block_size, kv_heads, head_dim)
    # sharded over fsdp then gathered: the audited bytes must equal the
    # analytic ring all-gather volume result*(g-1)/g exactly
    mesh = _mesh("fsdp")
    shape = (16, 8, 4, 32)
    kv_bytes = int(np.prod(shape)) * 4  # f32
    sharded = NamedSharding(mesh, P("fsdp"))
    replicated = NamedSharding(mesh, P())

    def gather(kv):
        # a real op first: a bare identity constraint collapses to a
        # single-device program and audits (correctly) as empty
        return jax.lax.with_sharding_constraint(kv * 2.0, replicated)

    spec = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharded)
    compiled = jax.jit(gather).lower(spec).compile()
    audit = audit_compiled("kv_gather", compiled)
    assert audit.by_kind == {"all-gather": 1}
    (op,) = audit.collectives
    assert op.result_bytes == kv_bytes
    assert op.bytes_moved == kv_bytes * 3 // 4
    assert audit.ici_bytes == kv_bytes * 3 // 4
    assert audit.dcn_bytes == 0


def test_summarize_audits_rolls_up_programs():
    mesh = _mesh("fsdp")
    compiled = _mis_pinned_compiled(mesh)
    a1 = audit_compiled("p1", compiled, contract=CONTRACT_ZERO)
    a2 = audit_compiled("p2", compiled)  # no contract: nothing violates
    s = summarize_audits([a1, a2])
    assert s["num_programs_audited"] == 2
    assert s["collectives_total"] == 2
    assert s["violations_total"] == 1
    assert s["violations"][0]["program"] == "p1"
    assert s["ici_bytes_total"] == 2 * (512 * 3 // 4)
    assert s["dcn_bytes_total"] == 0
    assert set(s["programs"]) == {"p1", "p2"}


# ---------------------------------------------------------------------- #
# ROADMAP (a): every serving program audited under fsdp/tensor layouts
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def tiny_serving_model():
    from accelerate_tpu.models import CausalLM, TransformerConfig

    cfg = TransformerConfig.tiny(max_seq_len=64)
    model = CausalLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return cfg, model, params


def _shard_params(params, mesh, axis):
    """Shard every leaf whose leading dim tiles over the mesh axis;
    replicate the rest (min-weight-size idiom, but explicit)."""
    size = mesh.shape[axis]

    def place(leaf):
        if leaf.ndim >= 1 and leaf.shape[0] % size == 0:
            spec = P(axis, *([None] * (leaf.ndim - 1)))
        else:
            spec = P()
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree.map(place, params)


def _audited_engine(model, params, axis):
    """Build a weight-sharded engine, run enough traffic to trace >= 2
    prefill buckets, the decode program, >= 1 verify width and the COW
    path, then audit every captured program. Returns (engine, audits)."""
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.serving.speculation import SpecConfig

    mesh = _mesh(axis)
    sharded = _shard_params(params, mesh, axis)
    engine = ServingEngine(
        model, sharded, max_slots=2, block_size=8, seed=0,
        spec_decode=SpecConfig(k=2),
    )
    # two prompt lengths -> two pow2 prefill buckets; the repetitive
    # prompts make the n-gram proposer fire, tracing a verify width
    engine.add_request([7, 8] * 3, max_new_tokens=6)
    engine.add_request([1, 2, 3] * 5, max_new_tokens=6)
    for _ in engine.stream():
        pass
    assert engine.trace_counts()["verify"] >= 1
    registry = ProgramRegistry()
    audits = engine.audit_programs(registry, emit=False)
    return engine, audits


@pytest.mark.parametrize("axis", ["fsdp", "tp"])
def test_all_serving_programs_audit_clean_under_weight_sharding(
    tiny_serving_model, axis,
):
    _, model, params = tiny_serving_model
    engine, audits = _audited_engine(model, params, axis)
    labels = set(audits)
    assert "serve_decode" in labels
    assert "serve_cow" in labels
    assert sum(1 for l in labels if l.startswith("serve_prefill_b")) >= 2
    assert sum(1 for l in labels if l.startswith("serve_verify_w")) >= 1
    for label, audit in audits.items():
        # the contract came from the actual leaf shardings
        assert audit.contract.origin == f"serve:{axis}"
        # zero involuntary reshards: every collective the compiler
        # emitted is explained by the weight layout — any finding names
        # the offending HLO op in the assertion message
        assert audit.clean, (
            f"{label}: involuntary reshards {audit.violations}"
        )
        # single slice: nothing may cross DCN
        assert audit.dcn_bytes == 0
        for op in audit.collectives:
            assert op.fabric == "ici"
            assert op.group_size <= 4


def test_replicated_serving_programs_have_zero_collectives(
    tiny_serving_model,
):
    # pure replicated serving (the common single-host engine): the
    # decode/verify/COW/prefill programs expect — and get — ZERO
    # cross-device collectives
    from accelerate_tpu.serving import ServingEngine

    _, model, params = tiny_serving_model
    engine = ServingEngine(model, params, max_slots=2, block_size=8)
    engine.add_request([1, 2, 3], max_new_tokens=2)
    for _ in engine.stream():
        pass
    registry = ProgramRegistry()
    audits = engine.audit_programs(registry, emit=False)
    assert audits
    for label, audit in audits.items():
        assert audit.contract.allowed == frozenset()
        assert audit.collectives == [], (
            f"{label}: unexpected collectives {audit.by_kind}"
        )
        assert audit.clean
    # the registry roll-up is reachable for soak reports / BENCH records
    summary = engine.audit_summary(registry)
    assert summary["num_programs_audited"] == len(audits)
    assert summary["violations_total"] == 0


def test_audit_smoke_decode_and_verify_clean_under_fsdp(
    tiny_serving_model,
):
    """The `make audit-smoke` assertion: paged decode + spec verify
    compile collective-clean under fsdp weight sharding on a 4-device
    CPU mesh (the CPU-feasible half of ROADMAP (a))."""
    _, model, params = tiny_serving_model
    engine, audits = _audited_engine(model, params, "fsdp")
    decode = audits["serve_decode"]
    verifies = [a for l, a in audits.items() if l.startswith("serve_verify_w")]
    assert verifies
    for audit in [decode] + verifies:
        assert audit.clean, (
            f"{audit.label}: involuntary reshards {audit.violations}"
        )
        assert audit.dcn_bytes == 0
