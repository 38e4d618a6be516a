"""The EvaByte cell (``serve-eva-longctx-sat``): a CPU rehearsal of the real
runner, reference, weights and readers at ``tiny_eva``'s size, the control and
the planted faults that must come out NOT correct, and the schema of the files
the cell brought. Run by hand, not tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_eva_cell.py -q
"""

import dataclasses
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import tiny_eva  # noqa: E402
from harness import cell as cells  # noqa: E402
from harness import common, evabyte_work, serve_runner  # noqa: E402
from test_program_trace import SPANS, cell_over  # noqa: E402

CELL = "serve-eva-longctx-sat"
CONFIG = "evabyte-6.5b-serve-1chip"
# EvaByte's config.json, every key of the catalog's row
PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768, "max_seq_length": 32768,
    "mixedp_attn": True, "model_type": "evabyte", "norm_add_unit_offset": True,
    "num_attention_heads": 32, "num_chunks": None, "num_hidden_layers": 32,
    "num_key_value_heads": 32, "num_pred_heads": 8, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000, "tie_word_embeddings": False,
    "vocab_size": 320, "window_size": 2048,
}
NEW_METRICS = {
    "eva_attn_device_share.decode", "eva_attn_device_share.prefill",
    "rollover_device_share.eva", "cache_rows_per_token.eva", "decode_roofline.eva"}


def load(path):
    with open(path) as f:
        return json.load(f)


def rehearse(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_NUM_CPU_DEVICES="1")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_eva.py"), *args],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


def test_sound_run_is_correct_and_windows_fill():
    result, log = rehearse()
    assert result["correct"] is True, log
    assert result["failed"] == 0 and result["attempted"] > 0
    assert {"setup_s", "serve_tokens_per_s"} <= set(result["metrics"])
    # float32 on the CPU serves the reference's own best byte
    assert " 0 tokens off the reference's best" in log


@pytest.mark.parametrize("fault", [
    "no_summaries", "own_chunks_twice", "roll_over_unwritten", "wrong_token",
    "one_token"])
def test_planted_faults_are_not_correct(fault):
    result, log = rehearse("--fault", fault)
    assert result["correct"] is False
    failed = [l for l in log.splitlines() if l.startswith("check ") and "FAILED" in l]
    assert any("widest_logit_gap" in l for l in failed), log
    if fault != "one_token":  # one byte of one request moves no mean
        assert any("served_token_mean_logit_gap" in l for l in failed), log


def test_the_lower_precision_control_changes_nothing_in_float32():
    """``bf16_stream`` turns the two flags off: at the rehearsal's float32
    there is nothing below to fall to, and the run stays correct. What it
    reads at the cell's bfloat16 is the chip's to say (PERF.md section 4)."""
    result, log = rehearse("--fault", "bf16_stream")
    assert result["correct"] is True, log


@pytest.mark.parametrize("seconds", [45.0, 20.0])
def test_every_sample_holds_an_answer_that_crossed_a_window(seconds):
    """The runner compares the longest finished request and
    ``reference_sample - 1`` others drawn by the seed. A roll-over gone
    wrong shows only in a request whose ANSWER crossed a window's end, so
    the sample is sized past the requests that never do: whatever the seed
    draws, one is among them (every request finishes: ``failed`` 0)."""
    from harness import traffic

    spec = cells.load_cell(CELL)["spec"]
    window = cells.load_cell(CELL)["config"]["window_size"]
    asked = [(len(r["prompt"]), r["max_new_tokens"]) for r in traffic.serve_requests(
        spec["traffic"], 5, seconds, 320)]
    # the last byte is sampled and never cached: positions reach p + new - 1,
    # and the window that position closes is summarised only if decoding goes on
    crossed = [(p + new - 2) // window > p // window for p, new in asked]
    longest = max(range(len(asked)), key=lambda i: sum(asked[i]))
    never = sum(1 for i, c in enumerate(crossed) if not c and i != longest)
    assert sum(crossed) >= 6
    assert crossed[longest] or spec["reference_sample"] - 1 > never, (
        spec["reference_sample"], never)


@pytest.mark.parametrize("seed", [3, 2**31 + 17, 99])
def test_int8_reference_puts_another_byte_first(seed):
    import jax.numpy as jnp

    cfg, spec = tiny_eva.config(), tiny_eva.serve_cell()
    reference, _ = common.modules_of(cfg)
    rng = np.random.default_rng(seed)
    # random continuations stand in for served bytes: the control reads the
    # byte the lower precision puts first, at every position
    seqs = [list(map(int, rng.integers(0, cfg["vocab_size"], 120))) for _ in range(4)]
    out = reference.served_token_gaps(cfg, seed, seqs, [8] * 4, jnp.float32,
                                      quant=True, rows=2, width=128)
    low = serve_runner.gap_stats(out["control_gap"])
    assert low["n"] == 4 * 112 and low["off_best"] >= 5, low
    # all three of the tiny cell's limits (a float32 program reads 0)
    assert all(low[name] > limit for name, limit in spec["limits"].items()), low
    assert all((m >= 0).all() for m in out["margin"])


# --------------------------------------------------------------------------- #
# schema of what the cell brought
# --------------------------------------------------------------------------- #
def test_configuration_holds_the_published_numbers_and_names_its_cuts():
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = load(os.path.join(ROOT, entry["file"]))
    assert cfg["source"] == entry["source"] and cfg["name"] == CONFIG
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == [
        "max_position_embeddings", "num_hidden_layers"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
            assert cfg[key] != value, f"{key} is listed as reduced and is not"
        else:
            assert cfg[key] == value, f"{key} differs from the source"
    for key in cfg["reduced"]:  # no width among the cuts
        assert not re.search(r"(_size|_dim|_rank|per_tok|_heads)$", key)
    # the floors ISSUE 30 sets: 16 layers, 16,384 positions
    assert cfg["num_hidden_layers"] >= 16 and cfg["max_position_embeddings"] >= 16384
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    for word in ("two pipeline stages", "8 slots", "16,384", "block_size 16"):
        assert word in cfg["deployment"], word
    assert {"head_dim", "rope", "chunk_key_summary", "chunk_value_summary",
            "what_a_query_sees", "head_layout", "initialiser"} <= set(cfg["assumed"])
    assert {"fp32_skip_add", "fp32_logits", "mixedp_attn"} <= set(cfg["dtypes"])
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200


def test_what_the_new_files_name_is_there():
    from accelerate_tpu.models import TransformerConfig

    cell = cells.load_cell(CELL)
    cfg, spec = cell["config"], cell["spec"]
    reference, weights = common.modules_of(cfg)
    for need in ("served_token_gaps", "train_reference", "leaf_norms",
                 "param_change_leaf_norms", "forward"):
        assert callable(getattr(reference, need)), need
    for need in ("make_tree", "abstract_tree", "layer_slice", "base_key",
                 "top_leaves", "spread_shardings"):
        assert callable(getattr(weights, need)), need
    # the runner's weight probe finds its leaf
    assert "mlp/down_proj" in weights.layer_slice(
        weights.base_key(3), tiny_eva.config(), 1, "float32")
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    assert set(cfg["program_fields"]) <= fields
    assert all(key in cfg for key in cfg["program_fields"].values())
    model_cfg = common.program_config(cfg, max_seq_len=spec["engine"]["max_seq_len"])
    assert (model_cfg.attention_class, model_cfg.chunk_size, model_cfg.window_size,
            model_cfg.num_pred_heads, model_cfg.norm_offset) == ("eva", 16, 2048, 8, True)
    # the published precision flags are what the program is built with
    assert (model_cfg.fp32_residual, model_cfg.fp32_logits) == (
        cfg["fp32_skip_add"], cfg["fp32_logits"]) == (True, True)
    assert spec["configuration"] == CONFIG and spec["kind"] == "serve"
    assert spec["engine"] == {"max_slots": 8, "block_size": 16, "max_seq_len": 16384}
    assert spec["traffic"]["prompt"] == {"median": 4096, "sigma": 0.6, "min": 1536, "max": 12288}
    assert spec["traffic"]["output"] == {"median": 768, "sigma": 0.5, "min": 256, "max": 2048}
    assert set(spec["limits"]) == set(spec["limits_why"])
    names = {m["name"] for m in cell["per_layer"]}
    assert names == NEW_METRICS | {
        "decode_step_ms.tput", "prefill_time_share.tput", "slot_occupancy.tput",
        "idle_schedule_share.tput", "idle_inputs_share.tput", "idle_fetch_share.tput",
        "idle_emit_share.tput", "idle_outside_engine_share.tput", "cold_compile_s"}
    # Mistral's needed work and its kernel's scope stay off
    assert not {"decode_roofline.tput", "paged_attn_device_share.tput"} & names
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
    # appended at the end of their lists
    assert [m["name"] for m in bench["per_layer"]][-5:] == [
        "eva_attn_device_share.decode", "eva_attn_device_share.prefill",
        "rollover_device_share.eva", "cache_rows_per_token.eva", "decode_roofline.eva"]
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG


def test_needed_work_counts_rows_not_positions():
    cfg = cells.load_cell(CELL)["config"]
    # 16 x (4 x 4096^2 + 3 x 4096 x 11,008) + the first head's 4096 x 320
    assert evabyte_work.matmul_params(cfg) == 16 * 202_375_168 + 4096 * 320
    assert evabyte_work.row_bytes(cfg) == 256 * 1024
    work = evabyte_work.decode_step_work(cfg, {"traced_rows": 12000.0, "traced_seated": 8.0})
    assert abs(work["bytes"] - (6.478e9 + 12000 * 262144)) < 5e6
    # 8 slots at position 8,000 each: 1,984 rows a slot, a quarter of the
    # positions; counted as positions the bytes would read over 100 %
    assert work["bytes"] < 2 * evabyte_work.matmul_params(cfg) + 64000 * 262144


def test_the_new_readers_read_stats_and_find_nothing_where_none_were_written(tmp_path):
    from readers import roofline_traced, scope_share_or_zero, span_stat

    rec = {"device_kind": "TPU v5 lite"}
    none = (rec, None, {})
    assert span_stat.read(*none, "atpu:serve.decode.inputs", "rows") is None
    assert scope_share_or_zero.read(*none, "jit__decode", "roll_over/") is None
    assert roofline_traced.read(
        *none, "jit__decode", "harness.evabyte_work:decode_step_work",
        "atpu:serve.decode.inputs", ["rows", "seated"]) is None
    # a trace of a program that writes `seated` and no `rows` (the parent's)
    cell = cell_over(tmp_path, SPANS, "spans")
    trace = {"trace": {"devices": {}}}
    assert span_stat.read(rec, trace, cell, "atpu:serve.decode.inputs", "seated") == 2.0
    assert span_stat.read(rec, trace, cell, "atpu:serve.decode.inputs", "rows") is None
    assert span_stat.read(rec, trace, cell, "atpu:serve.decode.inputs", "seated",
                          over="seated") == 1.0
    assert roofline_traced.read(
        rec, trace, cell, "jit__decode", "harness.evabyte_work:decode_step_work",
        "atpu:serve.decode.inputs", ["rows", "seated"]) is None
    # the decode program ran there and nothing of it lies under roll_over: 0
    assert scope_share_or_zero.read(rec, trace, cell, "jit__decode", "roll_over/") == 0.0
    assert scope_share_or_zero.read(rec, trace, cell, "jit__nothing", "x") is None
