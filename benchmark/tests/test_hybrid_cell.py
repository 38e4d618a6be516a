"""The hybrid expert cell (``train-moe-conv-1chip``): a CPU rehearsal of the
real runner, reference, weights and readers at ``tiny_hybrid``'s size, the
control and a planted fault that must come out NOT correct, and the schema
of the files the cell brought. Run by hand, not tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_hybrid_cell.py -q
"""

import dataclasses
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import tiny_hybrid  # noqa: E402
from harness import cell as cells  # noqa: E402
from harness import common, lfm2_work, traffic, train_runner  # noqa: E402

CELL = "train-moe-conv-1chip"
CONFIG = "lfm2-8b-a1b-train-1chip"
# LFM2-8B-A1B's config.json, every number of it (the catalog's row)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536, "model_type": "lfm2_moe",
    "layer_types": tiny_hybrid.PUBLISHED_LAYER_TYPES,
}


def load(path):
    with open(path) as f:
        return json.load(f)


def rehearse(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_NUM_CPU_DEVICES="1")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_hybrid.py"), *args],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


def test_sound_run_is_correct():
    result, log = rehearse()
    assert result["correct"] is True, log
    assert result["failed"] == 0 and result["attempted"] > 0
    assert {"setup_s", "train_tokens_per_s_per_chip"} <= set(result["metrics"])


def test_share_offset_off_by_one_is_not_correct():
    result, log = rehearse("--fault", "offset_off_by_one")
    assert result["correct"] is False
    failed = [l for l in log.splitlines() if l.startswith("check ") and "FAILED" in l]
    assert any("loss_gap_step1" in l for l in failed), log
    assert any("first_grad_worst_leaf_gap" in l for l in failed), log


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_int8_training_fails_the_comparison(seed):
    cfg, spec = tiny_hybrid.config(), tiny_hybrid.train_cell()
    reference, _ = common.modules_of(cfg)
    rows = traffic.token_rows(spec["traffic"], seed, cfg["vocab_size"])
    batches = [rows[2 * k:2 * k + 2] for k in range(3)]
    ref = reference.train_reference(cfg, spec["optimizer"], seed, batches)
    low = reference.train_reference(cfg, spec["optimizer"], seed, batches, quant=True)
    chk = common.Checks(lambda m: None)
    train_runner.compare(low, ref, spec["limits"], chk)
    failed = {name for name, _, _, ok in chk.rows if not ok}
    assert "first_grad_worst_leaf_gap" in failed, chk.rows
    same = common.Checks(lambda m: None)
    train_runner.compare(ref, ref, spec["limits"], same)
    assert same.ok


# --------------------------------------------------------------------------- #
# schema of what the cell brought
# --------------------------------------------------------------------------- #
def test_configuration_holds_the_published_numbers_and_names_its_cuts():
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = load(os.path.join(ROOT, entry["file"]))
    assert cfg["source"] == entry["source"] and cfg["name"] == CONFIG
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
            assert cfg[key] != value, f"{key} is listed as reduced and is not"
        else:
            assert cfg[key] == value, f"{key} differs from the source"
    for key in cfg["reduced"]:  # no width among the cuts
        assert not re.search(r"(_size|_dim|_rank|per_tok)$", key) or key == "vocab_size"
    # the floors of a cut: a whole period, >= 4 layers after the dense ones,
    # >= 8 experts in each expert layer, >= an eighth of the vocabulary
    period = PUBLISHED["layer_types"][2:6]
    assert cfg["layer_types"][cfg["num_dense_layers"]:] == period
    assert cfg["layer_types"] == PUBLISHED["layer_types"][1:6]
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert cfg["router_width"] == PUBLISHED["num_experts"]
    assert cfg["expert_offset"] + cfg["num_experts"] <= cfg["router_width"]
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    for word in ("4 chips", "experts 0-7", "16,383", "layers 1-5"):
        assert word in cfg["deployment"], word
    assert {"head_dim", "tie_word_embeddings", "expert_bias"} <= set(cfg["assumed"])


def test_what_the_new_files_name_is_there():
    from accelerate_tpu.models import TransformerConfig

    cell = cells.load_cell(CELL)
    cfg = cell["config"]
    reference, weights = common.modules_of(cfg)
    for need in ("train_reference", "leaf_norms", "param_change_leaf_norms"):
        assert callable(getattr(reference, need)), need
    for need in ("make_tree", "abstract_tree", "layer_slice", "base_key",
                 "spread_shardings"):
        assert callable(getattr(weights, need)), need
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    assert set(cfg["program_fields"]) <= fields
    assert all(key in cfg for key in cfg["program_fields"].values())
    model_cfg = common.program_config(cfg, max_seq_len=cell["spec"]["traffic"]["seq_len"])
    assert model_cfg.head_dim == 64 and model_cfg.moe_router == "sigmoid"
    assert cell["spec"]["configuration"] == CONFIG and cell["spec"]["remat"] == "dots_ragged"
    # ISSUE 26's optimizer, the dense cell's, and the one the rehearsal runs
    assert cell["spec"]["optimizer"] == tiny_hybrid.OPT == cells.load_cell(
        "train-dense-1chip")["spec"]["optimizer"]
    names = {m["name"] for m in cell["per_layer"]}
    assert {"moe_experts_device_share.train", "moe_route_device_share.train",
            "conv_device_share.train", "step_roofline.moe_train",
            "moe_experts_roofline.train", "attn_device_share.moe_train",
            "head_loss_device_share.moe_train", "mlp_device_share.moe_train",
            "optimizer_device_share.train",
            "train_step_ms", "data_wait_share", "cold_compile_s"} == names
    # the two that count a dense stack from num_hidden_layers stay off
    assert not {"mfu", "flash_roofline.train"} & names
    for metric in cell["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "readers", f"{metric['reader']}.py"))
        if "work" in metric.get("args", {}):
            assert callable(cells.named(metric["args"]["work"]))
    assert {m["name"] for m in cell["end_to_end"]} == {
        "train_tokens_per_s_per_chip", "setup_s"}


def test_new_per_layer_metrics_list_the_new_cell_alone():
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    for metric in bench["per_layer"]:
        spec = load(os.path.join(BENCH, "metrics", f"{metric['name']}.json"))
        args = json.dumps(spec.get("args", {}))
        if any(w in args for w in ("lfm2_work", "moe/", "ragged", "conv", "tied_head")):
            assert metric["workloads"] == [CELL], metric["name"]


def test_needed_work_counts_what_the_configuration_states():
    cfg = cells.load_cell(CELL)["config"]
    # 16.8 M + 44.0 M; 10.5 M + 8 x 11.01 M + router; 3 x (16.8 + 88.1 + 0.07) M;
    # 33.6 M of embedding: 507.9 M parameters held
    assert abs(lfm2_work.params_held(cfg) - 507.9e6) < 0.2e6
    rec = {"tokens_per_step_per_chip": 16384, "seq_len": 4096}
    step = lfm2_work.train_step_work(cfg, rec)
    assert 1.20e9 < step["flops"] / 16384 < 1.30e9  # ~1.25 GFLOP a token
    experts = lfm2_work.moe_experts_work(cfg, rec)
    assert abs(experts["flops"] / step["flops"] - 0.21) < 0.02
    # without a trace the readers find nothing to read and do not raise
    from readers import op_share, scope_share

    assert op_share.read(rec, None, {}, "jit__step", "^ragged-dot") is None
    assert scope_share.read(rec, None, {}, "jit__step", "moe/") is None
