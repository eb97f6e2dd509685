"""Model FLOP/s utilisation of the training cell: forward + backward
operations per position (costs.py, nothing recomputed) x positions per
second, over chips x the chip's published bf16 peak."""
from .. import costs, peaks
from . import train_rate


def read(ev):
    rate = train_rate.read(ev)
    if rate is None:
        return None
    per_pos = costs.ernie_train_flops_per_position(
        ev.config, int(ev.config["seq_len"]))
    pk = peaks.peaks_for(ev.device_kind)
    return 100.0 * per_pos * rate / (ev.chips * pk["bf16_flops"])
