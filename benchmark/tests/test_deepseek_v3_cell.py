"""The latent-attention / grouped-sparse-expert cell
(``serve-mla-moe-longctx-sat``): a CPU rehearsal of the real ``serve_hybrid``
runner, reference, weights and readers at ``tiny_deepseek_v3``'s size through
``run.execute``, the int8 control and the planted faults that must come out
NOT correct, and the schema of the files the cell brought. Run by hand, not
tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_deepseek_v3_cell.py -q
"""

import dataclasses
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import tiny_deepseek_v3 as tiny  # noqa: E402
from harness import cell as cells  # noqa: E402
from harness import common, deepseek_v3_work, serve_hybrid_runner  # noqa: E402

CELL = "serve-mla-moe-longctx-sat"
CONFIG = "deepseek-v3-serve-1chip"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "max_position_embeddings", "num_nextn_predict_layers"]
# the catalog row's ``config``, every key
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v3", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                     "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280,
}
NEW_METRICS = [
    "decode_roofline.mla_moe", "mla_decode_roofline.decode",
    "mla_prefill_roofline.prefill", "mla_device_share.decode",
    "mla_device_share.prefill", "moe_experts_roofline.mla_moe_decode",
    "cache_bytes_per_position.mla"]
SHARED_METRICS = {
    "decode_step_ms.tput", "prefill_time_share.tput", "slot_occupancy.tput",
    "step_exposed_ms.tput", "step_host_ms.tput", "decode_inputs_ms.tput",
    "decode_dispatch_ms.tput", "decode_fetch_copy_ms.tput",
    "idle_schedule_share.tput", "idle_inputs_share.tput", "idle_fetch_share.tput",
    "idle_emit_share.tput", "idle_outside_engine_share.tput",
    "idle_dispatch_share.tput", "idle_wait_share.tput", "cold_compile_s",
    "moe_experts_device_share.decode", "moe_route_device_share.decode",
    "moe_route_device_share.prefill",
    # the same reader over the same span stats as PR 38's cell (REVIEW 40)
    "experts_touched_share.gdn_moe"}


def load(path):
    with open(path) as f:
        return json.load(f)


def rehearse(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_NUM_CPU_DEVICES="1")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_deepseek_v3.py"), *args],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


def test_sound_run_is_correct_through_run_execute():
    result, log = rehearse()
    assert result["correct"] is True, log
    assert result["failed"] == 0 and result["attempted"] > 0
    assert {"setup_s", "serve_tokens_per_s"} <= set(result["metrics"])
    # float32 on the CPU serves the reference's own best token
    assert " 0 tokens off the reference's best" in log


@pytest.mark.parametrize("fault", [
    "no_k_rope", "no_mscale", "no_latent_norm", "no_group_limit",
    "no_shared_expert", "plain_rope", "wrong_token", "one_token"])
def test_planted_faults_are_not_correct(fault):
    result, log = rehearse("--fault", fault)
    assert result["correct"] is False
    failed = [l for l in log.splitlines() if l.startswith("check ") and "FAILED" in l]
    assert any("widest_logit_gap" in l for l in failed), log
    if fault != "one_token":  # one token of one request moves no mean
        assert any("served_token_mean_logit_gap" in l for l in failed), log


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_int8_reference_puts_another_token_first(seed):
    import jax
    import jax.numpy as jnp

    cfg, spec = tiny.config(), tiny.serve_cell()
    reference, weights = common.modules_of(cfg)
    rng = np.random.default_rng(seed)
    seqs = [list(map(int, rng.integers(0, cfg["vocab_size"], 120))) for _ in range(3)]
    out = reference.served_token_gaps(cfg, seed, seqs, [8] * 3, jnp.float32,
                                      quant=True, rows=1, width=256)
    low = serve_hybrid_runner.gap_stats(out["control_gap"])
    assert low["n"] == 3 * 112 and low["off_best"] >= 5, low
    assert all(low[name] > limit for name, limit in spec["limits"].items()), low
    assert all((m >= 0).all() for m in out["margin"])
    # the head was read at the served positions alone: the same gaps as the
    # one full forward pass gives there
    params = weights.make_tree(cfg, seed, jnp.float32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(reference.forward(params, cfg, jnp.asarray(seqs[:1])))[0]
    want = logits[7:119].max(-1) - logits[np.arange(7, 119), np.asarray(seqs[0][8:])]
    assert np.max(np.abs(out["gap"][0] - want)) < 1e-4


# --------------------------------------------------------------------------- #
# schema of what the cell brought
# --------------------------------------------------------------------------- #
def test_configuration_holds_the_published_numbers_and_names_its_cuts():
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = load(os.path.join(ROOT, entry["file"]))
    assert cfg["source"] == entry["source"] and cfg["name"] == CONFIG
    assert cfg["reduced"] == entry["reduced"] == REDUCED
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
            assert cfg[key] != value, f"{key} is listed as reduced and is not"
        else:
            assert cfg[key] == value, f"{key} differs from the source"
    for key in cfg["reduced"]:  # no width among the cuts
        assert not re.search(r"(_size|_dim|_rank|per_tok|_heads)$", key) or key == "vocab_size"
    assert cfg["num_hidden_layers"] == 5 and cfg["first_k_dense_replace"] == 1
    assert cfg["n_routed_experts"] == 16 and cfg["router_width"] == 256
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["shared_expert_intermediate_size"] == (
        cfg["moe_intermediate_size"] * cfg["n_shared_experts"])
    for word in ("16 chips share each layer", "16 of 256", "16,159", "layers 2-6",
                 "32 slots", "16,384", "block_size 16"):
        assert word in cfg["deployment"], word
    assert {"weights_and_cache", "rope", "e_score_correction_bias", "initialiser",
            "multi_token_prediction", "scan_layers", "fp32_logits",
            "latent_row_lanes"} <= set(cfg["assumed"])
    assert {"weights_and_cache", "router", "attention", "logits"} <= set(cfg["dtypes"])
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert deepseek_v3_work.params_held(cfg) == 4_565_721_088
    assert any("4.57 B" in note for note in cfg["notes"])
    assert "aot_memory" in cfg


def test_what_the_new_files_name_is_there():
    from accelerate_tpu.models import TransformerConfig

    cell = cells.load_cell(CELL)
    cfg, spec = cell["config"], cell["spec"]
    reference, weights = common.modules_of(cfg)
    for need in ("served_token_gaps", "train_reference", "leaf_norms", "forward"):
        assert callable(getattr(reference, need)), need
    for need in ("make_tree", "abstract_tree", "layer_slice", "base_key",
                 "top_leaves", "spread_shardings", "probe"):
        assert callable(getattr(weights, need)), need
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    assert set(cfg["program_fields"]) <= fields
    assert all(key in cfg for key in cfg["program_fields"].values())
    m = common.program_config(cfg, max_seq_len=spec["engine"]["max_seq_len"])
    assert (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim, m.qk_rope_head_dim,
            m.v_head_dim, m.head_dim, m.num_heads) == (1536, 512, 128, 64, 128, 192, 128)
    assert (m.num_experts, m.moe_router_width, m.num_experts_per_tok, m.moe_n_group,
            m.moe_topk_group, m.moe_router, m.moe_expert_bias,
            m.moe_routed_scaling_factor, m.moe_shared_intermediate_size) == (
                16, 256, 8, 8, 4, "sigmoid", True, 2.5, 2048)
    assert (m.num_layers, m.num_dense_layers, m.scan_layers, m.fp32_logits) == (
        5, 1, False, True)
    assert m.rope_scaling["type"] == "yarn" and m.rope_scaling["factor"] == 40
    assert spec["configuration"] == CONFIG and spec["kind"] == "serve_hybrid"
    assert spec["engine"] == {"max_slots": 32, "block_size": 16, "max_seq_len": 16384}
    assert spec["traffic"]["prompt"] == {"median": 6144, "sigma": 0.6, "min": 1024, "max": 14336}
    assert spec["traffic"]["output"] == {"median": 512, "sigma": 0.5, "min": 128, "max": 2048}
    assert spec["traffic"]["preseat"] == 32 and spec["reference_sample"] == 8
    assert spec["traffic"]["arrangement"] == 1 and spec["trace_seconds"] == 3.0
    assert set(spec["limits"]) == set(spec["limits_why"]) == {
        "served_token_mean_logit_gap", "worst_request_mean_logit_gap",
        "widest_logit_gap"}
    assert "rate_from" in spec and "predictions" in spec
    names = {m["name"] for m in cell["per_layer"]}
    assert names == set(NEW_METRICS) | SHARED_METRICS
    # per-head needed work and the span that reads nothing under decode-ahead
    assert not {"decode_roofline.tput", "launch_wake_ms.tput",
                "paged_attn_device_share.tput"} & names
    for metric in cell["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "readers", f"{metric['reader']}.py"))
        if "work" in metric.get("args", {}):
            assert callable(cells.named(metric["args"]["work"]))
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s", "setup_s"}


def test_new_per_layer_metrics_list_the_new_cell_alone():
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert listed[name]["workloads"] == [CELL], name
        assert listed[name]["moves"] == "serve_tokens_per_s"
    # appended at the end of their lists, 8 cells of 24
    assert [m["name"] for m in bench["per_layer"]][-len(NEW_METRICS):] == NEW_METRICS
    assert bench["workloads"][-1]["name"] == CELL and len(bench["workloads"]) == 8
    assert bench["workloads"][-1]["chips"] == 1
    assert bench["configs"][-1]["name"] == CONFIG
    for m in bench["per_layer"] + bench["end_to_end"]:
        if CELL in m.get("workloads", ()):  # appended, nothing before it moved
            assert m["workloads"][-1] == CELL


def test_the_runners_record_has_every_key_the_listed_readers_read(tmp_path):
    """One in-process run of the tiny cell through the runner: every reader
    of a metric the cell lists that reads the RECORD (not the trace) finds
    its keys there."""
    cell = tiny.serve_cell()
    loaded = cells.load_cell(cell["name"], tiny.make_root(str(tmp_path), cell))
    record, _ = serve_hybrid_runner.run(
        loaded, seed=2**31 + 5, seconds=1.0, trace=False,
        t_start=time.perf_counter(), say=lambda m: None)
    assert record["correct"] is True
    # three layers of one 128-lane float32 row; nothing a seat beside them
    assert record["state_bytes_per_slot"] == 0
    assert record["kv_bytes_per_token"] == 3 * 128 * 4
    read = 0
    for group in ("end_to_end", "per_layer"):
        for metric in loaded[group]:
            args = metric.get("args", {})
            keys = [args[k] for k in ("key", "num") if k in args] + list(args.get("den", []))
            for key in keys:
                assert key in record, (metric["name"], key)
                read += 1
    assert read >= 6
    # the work functions read what the readers hand them and the config
    rec = {"traced_seated": 32.0, "traced_rows": 240_000.0,
           "traced_experts_touched": 40.0, "traced_tokens": 8192.0}
    cfg = tiny.real()
    step = deepseek_v3_work.decode_step_work(cfg, rec)
    # the issue's reckoning: ~8.3 GB a step at ~10 of 16 experts a layer
    assert 8.0e9 < step["bytes"] < 8.6e9, step
    kernel = deepseek_v3_work.mla_decode_work(cfg, rec)
    assert abs(kernel["bytes"] - 240_000 * 5760) < 5e7
    assert kernel["flops"] == 240_000 * 5 * 278_528
    prefill = deepseek_v3_work.mla_prefill_work(cfg, rec)
    assert abs(prefill["flops"] - 13.7e12) < 0.2e12  # ~14 TFLOP at 8,192
    assert abs(deepseek_v3_work.moe_experts_decode_work(cfg, rec)["bytes"]
               - 40 * 44_040_192 * 2) < 2e7


def test_a_new_metrics_reader_finds_nothing_where_nothing_was_written(tmp_path):
    from readers import roofline_traced, span_stat
    from test_program_trace import SPANS, cell_over

    rec = {"device_kind": "TPU v5 lite"}
    args = ("jit__decode", "harness.deepseek_v3_work:mla_decode_work",
            "atpu:serve.decode.fetch", ["rows", "seated"], "^latent_decode")
    assert roofline_traced.read(rec, None, {}, *args) is None
    # a trace of a program that writes no ``cache_bytes`` (the parent's)
    cell = cell_over(tmp_path, SPANS, "spans")
    assert span_stat.read(rec, {"trace": {"devices": {}}}, cell,
                          "atpu:serve.decode.inputs", "cache_bytes", "positions") is None
