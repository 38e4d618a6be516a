"""Model FLOP/s utilisation: tokens per second per chip times the FLOPs a
token needs (forward + backward, recompute uncounted, attention causal) over
the chip's published bf16 peak."""

from harness import flops_bytes, peaks


def read(record, trace, cell):
    rate = record["tokens"] / record["window_s"] / record["chips"]
    need = flops_bytes.train_flops_per_token(cell["config"], record["seq_len"])
    return 100.0 * rate * need / peaks.peaks_for(record["device_kind"])["bf16_flops_per_s"]
