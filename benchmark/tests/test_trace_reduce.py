"""trace_reduce's arithmetic on a hand-written trace whose answers are known
(busy/idle, exposed collectives, kernel time inside a program, idle gaps by
host span), and its reading of a small trace recorded on the chip."""

import os

import pytest

import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import stats, trace_reduce  # noqa: E402

SYNTHETIC = os.path.join(HERE, "synthetic_2chip.xplane.textproto")
RECORDED = os.path.join(HERE, "recorded_train_1chip.xplane.pb")


def test_interval_arithmetic():
    merged = stats.merge_intervals([[0, 2], [1, 3], [5, 6], [6, 7], [9, 9]])
    assert merged == [[0, 3], [5, 7]] and stats.total(merged) == 5
    assert stats.subtract([[0, 10]], [[1, 2], [4, 6], [9, 12]]) == [
        [0, 1], [2, 4], [6, 9]]
    assert stats.subtract([[0, 1], [5, 8]], [[0, 6]]) == [[6, 8]]
    assert stats.clip([[0, 3], [5, 7]], 2, 6) == [[2, 3], [5, 6]]
    assert stats.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == 9
    assert stats.percentile([5], 95) == 5


def test_synthetic_trace_reduces_to_the_known_answers():
    red = trace_reduce.reduce(SYNTHETIC)
    ms = 1e-3
    assert red["window_s"] == pytest.approx(9 * ms)
    # chip 0: 0-3, 4-6, 8-9 = 6 ms busy; chip 1: 5 ms; mean 5.5 ms
    assert red["per_chip_busy_s"] == {0: pytest.approx(6 * ms), 1: pytest.approx(5 * ms)}
    assert red["busy_s"] == pytest.approx(5.5 * ms)
    exposed = trace_reduce.exposed_collective(red["trace"])
    # all-gather 2-3 wholly exposed, the async all-reduce 5-7 exposed for 6-7
    assert exposed["per_chip"][0] == pytest.approx(2 * ms)
    assert exposed["per_chip"][1] == 0
    took, n = trace_reduce.matching_time(
        red["trace"]["devices"][0]["ops"], "custom-call tpu_custom_call$")
    assert (took, n) == (pytest.approx(1 * ms), 1)
    assert red["breakdown"]["device_ops"][0] == ["fusion.7 fusion", pytest.approx(4 * ms)]
    # the while wraps 3 ms of children and keeps no self time
    assert ["while.1 while", pytest.approx(0, abs=1e-12)] in red["breakdown"]["device_ops"]
    gaps = dict(map(tuple, red["breakdown"]["idle_gaps"]))
    # idle on chip 0: 3-4 (under bench:next_batch), 6-7 (no span), 7-8 (bench:step)
    assert gaps == {"bench:next_batch": pytest.approx(1 * ms),
                    "bench:step": pytest.approx(1 * ms),
                    "(no span)": pytest.approx(1 * ms)}


def test_roofline_reader_on_the_synthetic_trace():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "roofline", os.path.join(os.path.dirname(HERE), "readers", "roofline.py"))
    roofline = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(roofline)
    cfg = {"hidden_size": 4096, "intermediate_size": 14336, "vocab_size": 32000,
           "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
           "num_hidden_layers": 3, "sliding_window": 4096}
    record = {"rows_per_chip": 8, "seq_len": 1024, "device_kind": "TPU v5 lite"}
    red = trace_reduce.reduce(SYNTHETIC)
    share = roofline.read(record, red, {"config": cfg}, program="step",
                          ops="custom-call tpu_custom_call$", work="harness.flops_bytes:flash_train_step_work")
    # 7 causal matmuls x 2*8*1024*32*128*512.5 FLOPs x 3 layers over 197e12,
    # against the one 1 ms custom call inside the one program
    need = 7 * 2 * 8 * 1024 * 32 * 128 * 512.5 * 3 / 197e12
    assert share == pytest.approx(100 * need / 1e-3)
    with pytest.raises(KeyError):
        roofline.read(dict(record, device_kind="TPU v9"), red, {"config": cfg},
                      program="step", ops="tpu_custom_call$", work="harness.flops_bytes:flash_train_step_work")


@pytest.mark.skipif(not os.path.isfile(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace_reads():
    red = trace_reduce.reduce(RECORDED)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert list(red["trace"]["devices"]) == [0]
    assert red["breakdown"]["device_ops"] and len(red["breakdown"]["device_ops"]) <= 10
    ops = red["trace"]["devices"][0]["ops"]
    took, n = trace_reduce.matching_time(ops, "custom-call tpu_custom_call$")
    # four Mosaic kernels (flash forward, its recompute, dq, dkv) per layer
    steps = len([m for m in red["trace"]["devices"][0]["modules"] if "jit__step" in m[0]])
    assert steps >= 9 and n >= 4 * 3 * (steps - 2) and took > 0
    assert not any(name.startswith("while") for name, _ in red["breakdown"]["device_ops"][:1])
    assert trace_reduce.op_label(
        '%attn.40 = (bf16[8]{0:T(8,128)(2,1)}, bf16[8]{0}) custom-call(bf16[8]{0} '
        '%custom-call.3), custom_call_target="tpu_custom_call"'
    ) == "attn.40 custom-call tpu_custom_call"
