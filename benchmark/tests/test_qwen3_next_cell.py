"""The hybrid linear-attention / sparse-expert cell (``serve-gdn-moe-sat``): a
CPU rehearsal of the real ``serve_hybrid`` runner, reference, weights and
readers at ``tiny_qwen3_next``'s size through ``run.execute``, the int8
control and the planted faults that must come out NOT correct, and the schema
of the files the cell brought. Run by hand, not tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_qwen3_next_cell.py -q
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

import tiny_qwen3_next as tiny  # noqa: E402
from harness import cell as cells  # noqa: E402
from harness import common, qwen3_next_work, serve_hybrid_runner  # noqa: E402
from test_program_trace import SPANS, cell_over  # noqa: E402

CELL = "serve-gdn-moe-sat"
CONFIG = "qwen3-next-80b-a3b-serve-1chip"
# the catalog row's ``config``, every key
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
NEW_METRICS = [
    "decode_roofline.gdn_moe", "moe_experts_roofline.decode",
    "gdn_step_roofline.decode", "gdn_scan_roofline.prefill",
    "moe_experts_device_share.decode", "moe_route_device_share.decode",
    "gdn_device_share.decode", "gdn_device_share.prefill",
    "attn_device_share.gdn_moe_decode", "experts_touched_share.gdn_moe"]
SHARED_METRICS = {
    "decode_step_ms.tput", "prefill_time_share.tput", "slot_occupancy.tput",
    "step_exposed_ms.tput", "step_host_ms.tput", "decode_inputs_ms.tput",
    "decode_dispatch_ms.tput", "decode_fetch_copy_ms.tput",
    "idle_schedule_share.tput", "idle_inputs_share.tput", "idle_fetch_share.tput",
    "idle_emit_share.tput", "idle_outside_engine_share.tput",
    "idle_dispatch_share.tput", "idle_wait_share.tput", "cold_compile_s",
    # the paged_decode kernel's own scope: the one cell that runs it over
    # pools laid out heads first (REVIEW 38)
    "paged_attn_device_share.tput"}


def load(path):
    with open(path) as f:
        return json.load(f)


def rehearse(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_NUM_CPU_DEVICES="1")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_qwen3_next.py"), *args],
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
    "no_state_handoff", "no_exp_g", "no_shared_gate", "no_attn_gate",
    "rope_whole_head", "wrong_token", "one_token"])
def test_planted_faults_are_not_correct(fault):
    result, log = rehearse("--fault", fault)
    assert result["correct"] is False
    failed = [l for l in log.splitlines() if l.startswith("check ") and "FAILED" in l]
    assert any("widest_logit_gap" in l for l in failed), log
    if fault != "one_token":  # one token of one request moves no mean
        assert any("served_token_mean_logit_gap" in l for l in failed), log
    if fault == "no_state_handoff":  # the number the hand-off has to fail
        assert any("first_decoded_mean_logit_gap" in l for l in failed), log


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_int8_reference_puts_another_token_first(seed):
    import jax.numpy as jnp

    cfg, spec = tiny.config(), tiny.serve_cell()
    reference, _ = common.modules_of(cfg)
    rng = np.random.default_rng(seed)
    seqs = [list(map(int, rng.integers(0, cfg["vocab_size"], 120))) for _ in range(3)]
    out = reference.served_token_gaps(cfg, seed, seqs, [8] * 3, jnp.float32,
                                      quant=True, rows=1, width=128)
    low = serve_hybrid_runner.gap_stats(out["control_gap"])
    assert low["n"] == 3 * 112 and low["off_best"] >= 5, low
    assert all(low[name] > limit for name, limit in spec["limits"].items()
               if name in low), low  # (the hand-off's number has a sample of its own)
    assert all((m >= 0).all() for m in out["margin"])
    # the head was read at the served positions alone: the same gaps as the
    # one full forward pass gives there
    import jax

    from harness import qwen3_next_weights as W

    params = W.make_tree(cfg, seed, jnp.float32)
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
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "max_position_embeddings"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
            assert cfg[key] != value, f"{key} is listed as reduced and is not"
        else:
            assert cfg[key] == value, f"{key} differs from the source"
    for key in cfg["reduced"]:  # no width among the cuts
        assert not re.search(r"(_size|_dim|_rank|per_tok|_heads)$", key) or key == "vocab_size"
    # the guide's floors: a whole period, 8 experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == cfg["full_attention_interval"] == 4
    assert cfg["layer_types"] == ["linear_attention"] * 3 + ["full_attention"]
    assert cfg["num_experts"] == 256 and cfg["router_width"] == 512
    assert cfg["vocab_size"] * 2 == cfg["published"]["vocab_size"]
    for word in ("2 chips share each layer", "256 of 512", "75,967", "layers 0-3",
                 "64 slots", "16,384", "block_size 16"):
        assert word in cfg["deployment"], word
    assert {"layer_types", "rope", "fused_projection_layout", "initialiser",
            "multi_token_prediction", "zero_centered_norm"} <= set(cfg["assumed"])
    assert {"weights_and_cache", "recurrent_state", "router"} <= set(cfg["dtypes"])
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    held = qwen3_next_work.params_held(cfg)
    assert held == 3_677_613_120
    assert any("3.68 B" in note for note in cfg["notes"])


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
    model_cfg = common.program_config(cfg, max_seq_len=spec["engine"]["max_seq_len"])
    assert (model_cfg.gdn_num_k_heads, model_cfg.gdn_num_v_heads,
            model_cfg.gdn_head_k_dim, model_cfg.gdn_head_v_dim,
            model_cfg.gdn_conv_kernel) == (16, 32, 128, 128, 4)
    assert (model_cfg.norm_offset, model_cfg.qk_norm, model_cfg.attn_output_gate,
            model_cfg.moe_shared_gate, model_cfg.moe_router) == (
                True, True, True, True, "softmax")
    assert (model_cfg.num_experts, model_cfg.moe_router_width,
            model_cfg.num_experts_per_tok, model_cfg.partial_rotary_factor) == (
                256, 512, 10, 0.25)
    # every layer a module of its own, the logits float32: both program
    # fields the file states and says why (``assumed``)
    assert (model_cfg.scan_layers, model_cfg.fp32_logits) == (False, True)
    assert {"scan_layers", "fp32_logits"} <= set(cfg["assumed"])
    assert spec["configuration"] == CONFIG and spec["kind"] == "serve_hybrid"
    assert spec["engine"] == {"max_slots": 64, "block_size": 16, "max_seq_len": 16384}
    assert spec["traffic"]["prompt"] == {"median": 3072, "sigma": 0.7, "min": 512, "max": 12288}
    assert spec["traffic"]["output"] == {"median": 512, "sigma": 0.5, "min": 128, "max": 2048}
    assert spec["traffic"]["preseat"] == 64 and spec["reference_sample"] == 8
    assert set(spec["limits"]) == set(spec["limits_why"])
    # the hand-off has a number of its own, from a sample of its own
    assert "first_decoded_mean_logit_gap" in spec["limits"]
    assert spec["handoff_sample"] == {"requests": 32, "decoded": 3, "width": 2048}
    assert "rate_from" in spec
    names = {m["name"] for m in cell["per_layer"]}
    assert names == set(NEW_METRICS) | SHARED_METRICS
    # the dense decoder's needed work and the span that reads nothing under
    # decode-ahead stay off
    assert not {"decode_roofline.tput", "launch_wake_ms.tput"} & names
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
    # appended at the end of their lists, 7 cells of 24
    assert [m["name"] for m in bench["per_layer"]][-len(NEW_METRICS):] == NEW_METRICS
    assert bench["workloads"][-1]["name"] == CELL and len(bench["workloads"]) == 7
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
    assert record["state_bytes_per_slot"] > 0 and record["kv_bytes_per_token"] > 0
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
    rec = {"traced_seated": 64.0, "traced_rows": 64 * 4096.0,
           "traced_experts_touched": 4 * 183.0, "traced_tokens": 4096.0}
    cfg = tiny.real()
    step = qwen3_next_work.decode_step_work(cfg, rec)
    # 183 experts a layer at 6.29 MB, mixers, routers, shared experts and
    # the half head, the state of 64 slots twice, 262,144 K/V rows
    assert 6.0e9 < step["bytes"] < 7.0e9, step
    assert abs(qwen3_next_work.moe_experts_decode_work(cfg, rec)["bytes"]
               - 4 * 183 * 3_145_728 * 2) < 2e7
    assert abs(qwen3_next_work.gdn_step_work(cfg, rec)["bytes"]
               - 2 * 64 * 3 * 32 * 128 * 128 * 4) < 1e7
    scan = qwen3_next_work.gdn_scan_work(cfg, rec)
    assert scan["flops"] > 0 and scan["bytes"] > 0


def test_the_new_reader_finds_nothing_where_nothing_was_written(tmp_path):
    from readers import roofline_scope_traced

    rec = {"device_kind": "TPU v5 lite"}
    args = ("jit__decode", "harness.qwen3_next_work:gdn_step_work", "(^|/)gdn/step/",
            "atpu:serve.decode.fetch", ["seated"])
    assert roofline_scope_traced.read(rec, None, {}, *args) is None
    # a trace of a program that writes no such stats on that span (the parent's)
    cell = cell_over(tmp_path, SPANS, "spans")
    assert roofline_scope_traced.read(rec, {"trace": {"devices": {}}}, cell, *args) is None
