"""Multi-tenant adapters: LoRA/QLoRA training + batched multi-adapter
serving (accelerate_tpu/adapters/).

The contracts under test: a fresh adapter (B = 0) is bitwise-invisible;
the frozen base takes identically-zero gradients (stop_gradient, not
just unoptimized); the optimizer carry holds ONLY adapter leaves;
adapter checkpoints are tiny committed artifacts; and the serving side
decodes N tenants in ONE batch through ONE compiled decode program —
per-tenant outputs bitwise equal to single-tenant references, zero
retraces as adapters churn.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.adapters import (
    AdapterRegistry,
    LoraConfig,
    adapter_dir,
    adapter_num_bytes,
    adapter_num_params,
    assert_adapter_only,
    build_lora_state,
    init_adapter,
    list_adapters,
    load_adapter,
    lora_loss_fn,
    save_adapter,
    target_shapes,
)
from accelerate_tpu.adapters.runtime import (
    A_KEY,
    B_KEY,
    lora_delta,
    pad_rank,
    stack_adapter,
)
from accelerate_tpu.models import CausalLM, TransformerConfig

_CFG = TransformerConfig.tiny()
_LCFG = LoraConfig(rank=4, alpha=8.0, target_modules=("q_proj", "v_proj"))


@pytest.fixture(scope="module")
def tiny():
    model = CausalLM(_CFG)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def _ids(batch=2, seq=16, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).integers(0, _CFG.vocab_size, (batch, seq)),
        jnp.int32,
    )


def _rand_adapter(seed, lcfg=_LCFG, cfg=_CFG):
    """An adapter with NONZERO B (init_adapter's B=0 contract makes fresh
    adapters invisible; tenant-distinguishing tests need visible ones)."""
    ad = init_adapter(jax.random.PRNGKey(seed), cfg, lcfg)
    return {
        t: {
            A_KEY: pair[A_KEY],
            B_KEY: 0.05 * jax.random.normal(
                jax.random.PRNGKey(seed * 977 + i), pair[B_KEY].shape
            ),
        }
        for i, (t, pair) in enumerate(sorted(ad.items()))
    }


# --------------------------------------------------------------------- #
# config + layout
# --------------------------------------------------------------------- #
def test_lora_config_validation_and_round_trip():
    with pytest.raises(ValueError):
        LoraConfig(rank=0)
    with pytest.raises(ValueError):
        LoraConfig(dropout=1.0)
    with pytest.raises(ValueError):
        LoraConfig(target_modules=("qproj",))
    with pytest.raises(ValueError):
        LoraConfig(target_modules=())
    cfg = LoraConfig(rank=16, alpha=32.0, target_modules=["q_proj"])
    assert cfg.scaling == 2.0
    assert LoraConfig.from_dict(cfg.to_dict()) == cfg


def test_init_adapter_injection_layout():
    lcfg = LoraConfig(rank=4, target_modules=(
        "q_proj", "k_proj", "o_proj", "gate_proj", "down_proj"
    ))
    ad = init_adapter(jax.random.PRNGKey(0), _CFG, lcfg)
    shapes = target_shapes(_CFG)
    L = _CFG.num_layers
    assert set(ad) == set(lcfg.target_modules)
    for t in lcfg.target_modules:
        in_dim, out_dim = shapes[t]
        assert ad[t][A_KEY].shape == (L, in_dim, 4)
        assert ad[t][B_KEY].shape == (L, 4, out_dim)
        # B = 0 is the init contract: delta exactly zero at birth
        assert not np.any(np.asarray(ad[t][B_KEY]))
    # k/v project to the KV width under GQA, q to the full head width
    assert shapes["q_proj"][1] == _CFG.num_heads * _CFG.head_dim
    assert shapes["k_proj"][1] == _CFG.num_kv_heads * _CFG.head_dim
    assert adapter_num_params(_CFG, lcfg) == sum(
        int(np.prod(l.shape)) for l in jax.tree.leaves(ad)
    )


def test_fresh_adapter_is_bitwise_invisible(tiny):
    model, params = tiny
    ids = _ids()
    ref = model.apply({"params": params}, ids)
    state = build_lora_state(
        init_adapter(jax.random.PRNGKey(1), _CFG, _LCFG), _LCFG, ids.shape[0]
    )
    out = model.apply({"params": params}, ids, lora=state)
    assert np.array_equal(np.asarray(ref), np.asarray(out))
    # and a trained (nonzero-B) adapter IS visible
    state2 = build_lora_state(_rand_adapter(7), _LCFG, ids.shape[0])
    out2 = model.apply({"params": params}, ids, lora=state2)
    assert not np.array_equal(np.asarray(ref), np.asarray(out2))


def test_per_slot_indexing_parity():
    """The gathered-stack math: each batch row reads ONLY its own slot's
    adapter — a mixed batch equals per-row single-adapter computations."""
    rng = np.random.default_rng(0)
    in_dim, out_dim, r, L = 8, 6, 4, 1
    pairs = [
        {
            A_KEY: jnp.asarray(rng.normal(size=(L, in_dim, r)), jnp.float32),
            B_KEY: jnp.asarray(rng.normal(size=(L, r, out_dim)), jnp.float32),
        }
        for _ in range(3)
    ]
    # stack rows: [identity, pair0, pair1, pair2]
    zero = jax.tree.map(jnp.zeros_like, pairs[0])
    stacked = jax.tree.map(
        lambda *ls: jnp.stack(ls, axis=1)[0], zero, *pairs
    )  # (rows, in, r) / (rows, r, out) for layer 0
    x = jnp.asarray(rng.normal(size=(4, 5, in_dim)), jnp.float32)
    slot_ids = jnp.asarray([2, 0, 3, 1], jnp.int32)
    scales = jnp.asarray([2.0, 1.5, 0.5, 1.0], jnp.float32)
    mixed = lora_delta(x, stacked, slot_ids, scales)
    for row in range(4):
        single = lora_delta(
            x[row:row + 1], stacked, slot_ids[row:row + 1], scales
        )
        assert np.array_equal(np.asarray(mixed[row]), np.asarray(single[0]))
    # row 0 is the identity: delta exactly zero
    assert not np.any(np.asarray(mixed[1]))


def test_rank_padding_is_exact():
    """Zero-padding a rank-2 adapter to r_max=8 changes nothing: the
    padded columns of A meet the padded rows of B at 0*0."""
    rng = np.random.default_rng(1)
    # stack-row layout: (rows, in, r) / (rows, r, out), one row
    a = jnp.asarray(rng.normal(size=(1, 8, 2)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(1, 2, 6)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, 3, 8)), jnp.float32)
    slot = jnp.zeros((1,), jnp.int32)
    scale = jnp.ones((1,), jnp.float32)
    small = lora_delta(x, {A_KEY: a, B_KEY: b}, slot, scale)
    padded = lora_delta(
        x,
        {
            A_KEY: pad_rank(a, axis=2, r_max=8),
            B_KEY: pad_rank(b, axis=1, r_max=8),
        },
        slot, scale,
    )
    # the padded products are exact zeros, but a rank-8 contraction sums in
    # another order than a rank-2 one: a few float32 ulps of |delta| ~ 10
    np.testing.assert_allclose(
        np.asarray(small), np.asarray(padded), rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError):
        pad_rank(a, axis=2, r_max=1)


# --------------------------------------------------------------------- #
# training: frozen base, adapter-only carry
# --------------------------------------------------------------------- #
def test_frozen_base_gradients_identically_zero(tiny):
    model, params = tiny
    from accelerate_tpu.utils.quantization import (
        QuantizationConfig,
        quantize_params,
    )

    qbase = quantize_params(
        params, QuantizationConfig(load_in_8bit=True, min_weight_size=256)
    )
    adapter = _rand_adapter(3)
    batch = {"input_ids": _ids()}

    base_grads = jax.grad(
        lambda b: lora_loss_fn(model, b, _LCFG)(adapter, batch)
    )(params)
    # identically zero — stop_gradient, not merely small
    for path, leaf in jax.tree_util.tree_flatten_with_path(base_grads)[0]:
        assert not np.any(np.asarray(leaf)), path

    # the quantized base path: adapter grads exist and are finite
    ad_grads = jax.grad(
        lora_loss_fn(model, qbase, _LCFG, compute_dtype=jnp.float32)
    )(adapter, batch)
    leaves = jax.tree.leaves(ad_grads)
    assert all(np.all(np.isfinite(np.asarray(l))) for l in leaves)
    # B is nonzero here, so BOTH a and b grads flow
    assert any(np.any(np.asarray(l)) for l in leaves)


def test_qlora_int8_loss_close_to_fp32(tiny):
    model, params = tiny
    from accelerate_tpu.utils.quantization import (
        QuantizationConfig,
        quantize_params,
    )

    adapter = _rand_adapter(4)
    batch = {"input_ids": _ids()}
    fp = float(lora_loss_fn(model, params, _LCFG)(adapter, batch))
    qbase = quantize_params(
        params, QuantizationConfig(load_in_8bit=True, min_weight_size=256)
    )
    q = float(
        lora_loss_fn(model, qbase, _LCFG, compute_dtype=jnp.float32)(
            adapter, batch
        )
    )
    assert abs(q - fp) / fp < 0.05, (q, fp)


@pytest.mark.parametrize("optimizer", ["adamw", "fused_adamw"])
def test_unified_step_adapter_only_carry(optimizer):
    """The tentpole training contract: ONLY adapter leaves in the carry,
    threading the existing unified_step (fused_adamw epilogue applies or
    declines without error), loss decreasing over an int8 frozen base.

    The adapter tree must be the LAST tree prepared before init_carry —
    prepare() re-infers shardings per call and unified_step pins the
    carry to the most recent set.
    """
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.utils.quantization import (
        QuantizationConfig,
        quantize_params,
    )

    model = CausalLM(_CFG)
    acc = Accelerator(mixed_precision="bf16")
    base = acc.prepare(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
            "params"
        ]
    )
    qbase = quantize_params(base, QuantizationConfig(load_in_8bit=True))
    adapter = acc.prepare(init_adapter(jax.random.PRNGKey(1), _CFG, _LCFG))
    assert_adapter_only(adapter, _LCFG)
    if optimizer == "fused_adamw":
        from accelerate_tpu.ops.fused import fused_adamw

        opt = acc.prepare(fused_adamw(1e-3))
    else:
        opt = acc.prepare(optax.adamw(1e-3))
    carry = acc.init_carry(adapter, opt)
    assert_adapter_only(carry["params"], _LCFG)
    step = acc.unified_step(
        lora_loss_fn(model, qbase, _LCFG, compute_dtype=jnp.bfloat16),
        max_grad_norm=1.0,
    )
    batch = {"input_ids": _ids(seed=2)}
    losses = []
    # the fused epilogue is a Pallas kernel: off-chip it runs only under
    # the tests' interpreter context
    from accelerate_tpu.ops.flash_attention import kernel_interpret_mode

    with kernel_interpret_mode():
        for _ in range(5):
            carry, metrics = step(carry, batch)
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
    assert_adapter_only(carry["params"], _LCFG)
    with pytest.raises(AssertionError):
        assert_adapter_only({"q_proj": {}, "extra": {}}, _LCFG)


# --------------------------------------------------------------------- #
# checkpoints: tiny committed artifacts
# --------------------------------------------------------------------- #
def test_adapter_save_restore_round_trip(tiny, tmp_path):
    _, params = tiny
    from accelerate_tpu.checkpointing import save_model_weights

    adapter = _rand_adapter(5)
    base_dir = str(tmp_path)
    path = save_adapter(base_dir, "tenant-a", adapter, _LCFG)
    assert os.path.basename(path) == "adapter_tenant-a"
    assert not os.path.exists(path + ".tmp")  # work dir committed away
    loaded, lcfg2 = load_adapter(path)
    assert lcfg2 == _LCFG
    for t in _LCFG.target_modules:
        for k in (A_KEY, B_KEY):
            assert np.array_equal(
                np.asarray(adapter[t][k]), np.asarray(loaded[t][k])
            ), (t, k)
    assert list_adapters(base_dir) == {"tenant-a": path}
    with pytest.raises(ValueError):
        save_adapter(base_dir, "a/b", adapter, _LCFG)

    # acceptance: committed adapter bytes <= 2% of the base checkpoint at
    # rank 16. Adapter bytes grow LINEARLY in hidden while the base grows
    # quadratically, so the check runs at a width where the ratio is
    # representative (at hidden=128 even the tiny base is only ~2.6 MB
    # and the constant-factor config json dominates).
    cfg = TransformerConfig.tiny(hidden_size=512)
    wide = CausalLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    base_ckpt = str(tmp_path / "base")
    save_model_weights(wide, base_ckpt)

    def du(d):
        return sum(
            os.path.getsize(os.path.join(r, f))
            for r, _, fs in os.walk(d) for f in fs
        )

    lcfg16 = LoraConfig(rank=16, target_modules=("q_proj", "v_proj"))
    path16 = save_adapter(
        base_dir, "rank16", init_adapter(jax.random.PRNGKey(2), cfg, lcfg16),
        lcfg16,
    )
    assert du(path16) <= 0.02 * du(base_ckpt), (du(path16), du(base_ckpt))


def test_load_adapter_requires_commit(tmp_path):
    from accelerate_tpu.checkpoint_async.commit import work_dir_for

    final = adapter_dir(str(tmp_path), "ghost")
    os.makedirs(work_dir_for(final))  # in-flight save, never committed
    with pytest.raises(FileNotFoundError):
        load_adapter(final)
    assert list_adapters(str(tmp_path)) == {}


# --------------------------------------------------------------------- #
# registry: residency, refcounts, LRU
# --------------------------------------------------------------------- #
def test_registry_load_evict_refcount_lru():
    reg = AdapterRegistry(
        _CFG, capacity=2, max_rank=4, target_modules=_LCFG.target_modules
    )
    a, b, c = (_rand_adapter(s) for s in (10, 11, 12))
    reg.load("a", a, _LCFG)
    reg.load("b", b, _LCFG)
    assert reg.resident("a") and reg.resident("b")
    assert reg.resident(None)  # base model is always resident (row 0)
    assert reg.slot_of(None) == 0
    assert sorted(reg.resident_names()) == ["a", "b"]
    assert reg.slot_of("a") != reg.slot_of("b") != 0

    reg.acquire("a")
    with pytest.raises(RuntimeError):
        reg.evict("a")  # in-flight requests pin it
    # full + "a" pinned: LRU evicts "b" (refcount 0)
    reg.load("c", c, _LCFG)
    assert not reg.resident("b") and reg.resident("c")
    assert reg.evict_total == 1

    reg.acquire("c")
    with pytest.raises(RuntimeError):
        reg.load("d", _rand_adapter(13), _LCFG)  # every slot pinned
    reg.release("a")
    reg.release("c")
    reg.evict("c")
    assert not reg.resident("c")
    assert reg.hbm_bytes() > 0


def test_registry_validates_rank_targets_shapes():
    reg = AdapterRegistry(
        _CFG, capacity=2, max_rank=4, target_modules=("q_proj", "v_proj")
    )
    with pytest.raises(ValueError):
        reg.load("r", _rand_adapter(1, LoraConfig(rank=8)),
                 LoraConfig(rank=8))  # rank > max_rank
    wide = LoraConfig(rank=4, target_modules=("q_proj", "o_proj"))
    with pytest.raises(ValueError):
        reg.load("t", _rand_adapter(1, wide), wide)  # o_proj not in registry
    bad = _rand_adapter(1)
    bad["q_proj"][A_KEY] = bad["q_proj"][A_KEY][:, :8, :]
    with pytest.raises(ValueError):
        reg.load("s", bad, _LCFG)  # leaf shape vs model layout
    # a rank-2 adapter zero-pads into the rank-4 stacks
    l2 = LoraConfig(rank=2, target_modules=_LCFG.target_modules)
    reg.load("small", _rand_adapter(2, l2), l2)
    assert reg.resident("small")


# --------------------------------------------------------------------- #
# serving: admission gating, multi-tenant parity, zero retraces
# --------------------------------------------------------------------- #
def _engine(tiny, capacity=4, **kw):
    from accelerate_tpu.serving import ServingEngine

    model, params = tiny
    reg = AdapterRegistry(
        _CFG, capacity=capacity, max_rank=_LCFG.rank,
        target_modules=_LCFG.target_modules,
    )
    kw.setdefault("max_slots", 4)
    kw.setdefault("block_size", 8)
    return ServingEngine(model, params, adapters=reg, **kw), reg


def _serve(engine, reqs, seed=0):
    """reqs: list of (adapter_name | None); returns {rid: tokens}."""
    rng = np.random.default_rng(seed)
    rids = [
        engine.add_request(
            rng.integers(1, 50, size=4 + i).tolist(),
            max_new_tokens=6, adapter=name,
        )
        for i, name in enumerate(reqs)
    ]
    for _ in engine.stream():
        pass
    return {rid: engine.result(rid) for rid in rids}


def test_scheduler_gates_admission_on_residency(tiny):
    engine, reg = _engine(tiny)
    rid = engine.add_request([1, 2, 3], max_new_tokens=4, adapter="t0")
    engine.step()
    # not resident: the request stays queued, attributed visibly
    assert engine.result(rid) is None
    assert engine.scheduler.blocked_reasons["adapter_not_resident"] >= 1
    assert (
        engine._gauge_fields()["admission_blocked_adapter_not_resident_total"]
        >= 1
    )
    reg.load("t0", _rand_adapter(20), _LCFG)
    for _ in engine.stream():
        pass
    assert engine.result(rid) is not None
    # naming an adapter without a registry is a loud error
    from accelerate_tpu.serving import ServingEngine

    model, params = tiny
    bare = ServingEngine(model, params, max_slots=2, block_size=8)
    with pytest.raises(ValueError):
        bare.add_request([1, 2], adapter="t0")


def test_multi_adapter_batch_bitwise_matches_single_tenant(tiny):
    """THE serving acceptance: >= 3 distinct adapters + the base in ONE
    batch; each tenant's tokens equal a single-tenant reference run."""
    adapters = {f"t{i}": _rand_adapter(30 + i) for i in range(3)}

    engine, reg = _engine(tiny)
    for name, ad in adapters.items():
        reg.load(name, ad, _LCFG)
    mixed = _serve(engine, ["t0", "t1", "t2", None], seed=7)
    assert engine.trace_counts()["decode"] == 1

    # one single-tenant reference engine per adapter, same prompts
    for i, name in enumerate(["t0", "t1", "t2", None]):
        ref_engine, ref_reg = _engine(tiny)
        if name is not None:
            ref_reg.load(name, adapters[name], _LCFG)
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, 50, size=4 + j).tolist() for j in range(4)]
        rid = ref_engine.add_request(
            prompts[i], max_new_tokens=6, adapter=name
        )
        for _ in ref_engine.stream():
            pass
        assert ref_engine.result(rid) == list(mixed.values())[i], name
    # distinct adapters really decode differently (B is nonzero)
    outs = list(mixed.values())
    assert len({tuple(o) for o in outs}) > 1


def test_zero_decode_retraces_across_adapter_churn(tiny):
    engine, reg = _engine(tiny)
    reg.load("t0", _rand_adapter(40), _LCFG)
    _serve(engine, ["t0", None])  # warmup compiles prefill + decode
    warm = dict(engine.trace_counts())
    for i in (1, 2, 3):
        reg.load(f"t{i}", _rand_adapter(40 + i), _LCFG)
    _serve(engine, ["t1", "t2", "t3", None], seed=1)
    reg.load("t4", _rand_adapter(44), _LCFG)  # LRU-evicts a cold tenant
    _serve(engine, ["t4", "t1"], seed=2)
    assert engine.trace_counts()["decode"] == warm["decode"] == 1
    assert reg.load_total == 5 and reg.evict_total >= 1


def test_serve_telemetry_carries_adapter_id(tiny):
    from accelerate_tpu.telemetry import (
        PrometheusTextSink,
        StepTelemetry,
        TelemetryConfig,
    )

    tel = StepTelemetry(TelemetryConfig())
    sink = PrometheusTextSink(path=None)
    tel.add_sink(sink)
    engine, reg = _engine(tiny, telemetry=tel, gauge_interval=1)
    reg.load("t0", _rand_adapter(50), _LCFG)
    _serve(engine, ["t0", None])
    records = [r for r in tel.records if r.get("kind") == "serve"]
    assert {r["adapter_id"] for r in records} == {"t0", None}
    spans = {s.request_id: s for s in engine.span_log.closed}
    assert sorted(
        (s.adapter_id for s in spans.values()), key=lambda a: a or ""
    ) == [None, "t0"]
    text = sink.render()
    assert (
        'accelerate_tpu_serve_requests_total{adapter="t0"} 1' in text
    ), text
    assert (
        'accelerate_tpu_serve_requests_total{adapter="none"} 1' in text
    ), text
    assert (
        'accelerate_tpu_serve_adapters_resident{label="serve"} 1.0' in text
    ), text
    tel.close()


# --------------------------------------------------------------------- #
# interop + end-to-end
# --------------------------------------------------------------------- #
def test_peft_export_layout_map():
    from accelerate_tpu.utils.hf_interop import adapter_to_peft, peft_to_adapter

    lcfg = LoraConfig(rank=4, target_modules=("q_proj", "gate_proj"))
    ad = init_adapter(jax.random.PRNGKey(0), _CFG, lcfg)
    sd = adapter_to_peft(ad, lcfg, _CFG)
    L = _CFG.num_layers
    assert len(sd) == 2 * 2 * L
    h, q_dim = target_shapes(_CFG)["q_proj"]
    f = _CFG.intermediate_size
    # PEFT/torch layouts: lora_A (r, in), lora_B (out, r); attention
    # modules under self_attn, MLP modules under mlp
    k = "base_model.model.model.layers.0.self_attn.q_proj.lora_A.weight"
    assert sd[k].shape == (4, h)
    k = "base_model.model.model.layers.1.self_attn.q_proj.lora_B.weight"
    assert sd[k].shape == (q_dim, 4)
    k = "base_model.model.model.layers.0.mlp.gate_proj.lora_A.weight"
    assert sd[k].shape == (4, h)
    assert sd[
        "base_model.model.model.layers.1.mlp.gate_proj.lora_B.weight"
    ].shape == (f, 4)
    # torch layout is the TRANSPOSE of the native leaf, layer-sliced
    assert np.array_equal(
        sd["base_model.model.model.layers.0.self_attn.q_proj.lora_A.weight"],
        np.asarray(ad["q_proj"][A_KEY][0]).T,
    )
    back = peft_to_adapter(sd, lcfg, _CFG)
    for t in lcfg.target_modules:
        for key in (A_KEY, B_KEY):
            assert np.array_equal(np.asarray(ad[t][key]), back[t][key])


@pytest.mark.slow
def test_lora_smoke_end_to_end(tiny, tmp_path):
    """The `make lora-smoke` path: train an adapter through unified_step,
    commit its checkpoint, load it into an engine next to a second
    adapter, and decode token-for-token equal to a single-tenant
    reference engine serving the same trained adapter."""
    import optax

    from accelerate_tpu import Accelerator, AcceleratorState

    model, params = tiny
    acc = Accelerator(mixed_precision="bf16")
    base = acc.prepare(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
            "params"
        ]
    )
    adapter = acc.prepare(init_adapter(jax.random.PRNGKey(1), _CFG, _LCFG))
    opt = acc.prepare(optax.adamw(1e-2))
    carry = acc.init_carry(adapter, opt)
    step = acc.unified_step(lora_loss_fn(model, base, _LCFG))
    batch = {"input_ids": _ids(seed=3)}
    first = last = None
    for _ in range(8):
        carry, metrics = step(carry, batch)
        last = float(metrics["loss"])
        first = first if first is not None else last
    assert last < first
    trained = jax.tree.map(np.asarray, carry["params"])
    path = save_adapter(str(tmp_path), "trained", trained, _LCFG)
    # the engine below lives on one device: drop the live 8-device mesh
    AcceleratorState._reset_state(reset_partial_state=True)

    loaded, lcfg = load_adapter(path)
    engine, reg = _engine(tiny)
    reg.load("trained", loaded, lcfg)
    reg.load("other", _rand_adapter(60), _LCFG)
    mixed = _serve(engine, ["trained", "other", None], seed=9)
    assert engine.trace_counts()["decode"] == 1

    ref_engine, ref_reg = _engine(tiny)
    ref_reg.load("trained", loaded, lcfg)
    rng = np.random.default_rng(9)
    prompt = rng.integers(1, 50, size=4).tolist()
    rid = ref_engine.add_request(prompt, max_new_tokens=6, adapter="trained")
    for _ in ref_engine.stream():
        pass
    assert ref_engine.result(rid) == list(mixed.values())[0]
