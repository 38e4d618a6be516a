"""The mixed window / full attention sparse-expert cell
(``serve-swa-moe-mixed-sat``): a CPU rehearsal of the real ``serve_hybrid``
runner, reference, weights and readers at ``tiny_smallthinker``'s size through
``run.execute``, the int8 control and the planted faults that must come out
NOT correct, and the schema of the files the cell brought. Run by hand, not
tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_smallthinker_cell.py -q
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

import tiny_smallthinker as tiny  # noqa: E402
from harness import cell as cells  # noqa: E402
from harness import common, serve_hybrid_runner, smallthinker_work  # noqa: E402

CELL = "serve-swa-moe-mixed-sat"
CONFIG = "smallthinker-21b-a3b-serve-1chip"
PERIOD = [0, 1, 1, 1]
# the catalog row's ``config``, every key
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_layout": PERIOD * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": PERIOD * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False, "vocab_size": 151936,
}
NEW_METRICS = [
    "decode_roofline.swa_moe", "moe_experts_roofline.swa_moe_decode",
    "window_attn_device_share.decode", "full_attn_device_share.decode",
    "window_attn_device_share.prefill", "cache_rows_per_token.swa",
    "full_attn_device_share.prefill"]
SHARED_METRICS = {
    "decode_step_ms.tput", "prefill_time_share.tput", "slot_occupancy.tput",
    "step_exposed_ms.tput", "step_host_ms.tput", "decode_inputs_ms.tput",
    "decode_dispatch_ms.tput", "decode_fetch_copy_ms.tput",
    "idle_schedule_share.tput", "idle_inputs_share.tput", "idle_fetch_share.tput",
    "idle_emit_share.tput", "idle_outside_engine_share.tput",
    "idle_dispatch_share.tput", "idle_wait_share.tput", "cold_compile_s",
    "moe_experts_device_share.decode", "moe_route_device_share.decode",
    "moe_route_device_share.prefill", "experts_touched_share.gdn_moe",
    "paged_attn_device_share.tput"}


def load(path):
    with open(path) as f:
        return json.load(f)


def rehearse(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_NUM_CPU_DEVICES="1")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_smallthinker.py"), *args],
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
    "no_band", "rope_on_full", "rope_off_window", "router_post_attn", "silu_gate",
    "ring_not_written", "ring_one_block_short", "wrong_token", "one_token"])
def test_planted_faults_are_not_correct(fault):
    result, log = rehearse("--fault", fault)
    assert result["correct"] is False
    failed = [l for l in log.splitlines() if l.startswith("check ") and "FAILED" in l]
    assert any("widest_logit_gap" in l for l in failed), log
    if fault == "ring_not_written":  # the number the hand-off has to fail
        assert any("first_decoded_mean_logit_gap" in l for l in failed), log


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_int8_reference_puts_another_token_first(seed):
    import jax
    import jax.numpy as jnp

    from harness import smallthinker_weights as W

    cfg, spec = tiny.config(), tiny.serve_cell()
    reference, _ = common.modules_of(cfg)
    rng = np.random.default_rng(seed)
    seqs = [list(map(int, rng.integers(0, cfg["vocab_size"], 120))) for _ in range(3)]
    out = reference.served_token_gaps(cfg, seed, seqs, [8] * 3, jnp.float32,
                                      quant=True, rows=1, width=128)
    low = serve_hybrid_runner.gap_stats(out["control_gap"])
    assert low["n"] == 3 * 112 and low["off_best"] >= 5, low
    assert all(low[name] > limit for name, limit in spec["limits"].items()
               if name in low), low
    # the head was read at the served positions alone: the same gaps as the
    # one full forward pass gives there
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
        "num_hidden_layers", "sliding_window_layout", "rope_layout"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg[key] != value, f"{key} is listed as reduced and is not"
        else:
            assert cfg[key] == value, f"{key} differs from the source"
    assert cfg["published"]["num_hidden_layers"] == 52
    for key in cfg["reduced"]:  # no width among the cuts
        assert not re.search(r"(_size|_dim|_rank|per_tok|_heads|experts)$", key)
    # two whole periods, every expert, the whole vocabulary and context
    assert cfg["num_hidden_layers"] == 8
    assert cfg["sliding_window_layout"] == cfg["rope_layout"] == PERIOD * 2
    assert cfg["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 + [
        "full_attention"] + ["sliding_attention"] * 3
    for word in ("seven pipeline stages", "8, 8, 8, 8, 8, 8 and 4", "first stage",
                 "32 slots", "16,384", "block_size 16"):
        assert word in cfg["deployment"], word
    assert {"attention_bias_and_qk_norm", "rope", "hidden_act", "router_pre_attention",
            "router_kind", "initialiser", "qk_gain", "scan_layers", "fp32_logits",
            "secondary_experts", "intermediate_size"} <= set(cfg["assumed"])
    assert {"weights_and_cache", "router", "attention", "logits"} <= set(cfg["dtypes"])
    assert "aot_memory" in cfg
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert smallthinker_work.params_held(cfg) == 3_966_937_600
    assert any("3.97 B" in note for note in cfg["notes"])
    assert any("share-sum" in note for note in cfg["notes"])


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
    assert (model_cfg.sliding_window, model_cfg.rope_layout, model_cfg.mlp_activation,
            model_cfg.moe_router_pre_attention, model_cfg.moe_router) == (
                4096, (False, True, True, True) * 2, "relu", True, "softmax")
    assert (model_cfg.num_experts, model_cfg.num_experts_per_tok,
            model_cfg.moe_intermediate_size, model_cfg.moe_router_width) == (
                64, 6, 768, None)
    assert (model_cfg.scan_layers, model_cfg.fp32_logits) == (False, True)
    assert spec["configuration"] == CONFIG and spec["kind"] == "serve_hybrid"
    assert spec["engine"] == {"max_slots": 32, "block_size": 16, "max_seq_len": 16384}
    assert spec["traffic"]["prompt"] == {"median": 4096, "sigma": 1.0, "min": 256, "max": 14336}
    assert spec["traffic"]["output"] == {"median": 384, "sigma": 0.6, "min": 64, "max": 1536}
    assert spec["traffic"]["preseat"] == 32 and spec["traffic"]["arrangement"] == 1
    assert spec["reference_sample"] == 8
    assert set(spec["limits"]) == set(spec["limits_why"])
    assert "first_decoded_mean_logit_gap" in spec["limits"]
    assert spec["handoff_sample"]["decoded"] == 3
    assert "rate_from" in spec and "issue" in spec["predictions"]
    names = {m["name"] for m in cell["per_layer"]}
    assert names == set(NEW_METRICS) | SHARED_METRICS
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
    at = [m["name"] for m in bench["per_layer"]].index(NEW_METRICS[0])
    assert [m["name"] for m in bench["per_layer"]][at:at + len(NEW_METRICS)] == NEW_METRICS
    assert CELL in [w["name"] for w in bench["workloads"]]
    assert next(w for w in bench["workloads"] if w["name"] == CELL)["chips"] == 1


def test_the_work_functions_read_what_the_readers_hand_them():
    """At the published widths: a step of 32 seats standing at 6,000 positions
    on average that touched 61 of 64 experts a layer."""
    cfg = tiny.real()
    rec = {"traced_seated": 32.0, "traced_rows": 32 * 6000.0,
           "traced_window_rows": 32 * 3500.0, "traced_experts_touched": 8 * 61.0}
    step = smallthinker_work.decode_step_work(cfg, rec)
    # 488 experts at 11.8 MB, attention 8 x 41.9 MB, the head 778 MB, routers;
    # 2 x 192,000 + 6 x 112,000 rows of 2,048 B
    assert 8.5e9 < step["bytes"] < 9.5e9, step
    assert abs(smallthinker_work.moe_experts_decode_work(cfg, rec)["bytes"]
               - 8 * 61 * 5_898_240 * 2) < 3e7
    # the mechanism: what the rows would be if every layer held every row
    uniform = smallthinker_work.decode_step_work(
        cfg, {**rec, "traced_window_rows": rec["traced_rows"]})
    assert uniform["bytes"] - step["bytes"] == 6 * 32 * 2500 * 2048


def test_the_readers_find_nothing_where_nothing_was_written():
    from readers import roofline_traced, span_stat

    rec = {"device_kind": "TPU v5 lite"}
    args = ("jit__decode", "harness.smallthinker_work:decode_step_work",
            "atpu:serve.decode.fetch", ["experts_touched", "rows", "window_rows", "seated"])
    assert roofline_traced.read(rec, None, {}, *args) is None
    assert span_stat.read(rec, None, {}, "atpu:serve.decode.inputs", "layer_rows",
                          "layer_positions") is None
