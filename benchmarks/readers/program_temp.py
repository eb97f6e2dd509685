"""Temporaries of the compiled step, as the program's own memory analysis
gives them, over the chip's published capacity."""
from .. import peaks


def read(ev):
    if ev.program_temp_bytes is None:
        return None
    return 100.0 * ev.program_temp_bytes / peaks.peaks_for(
        ev.device_kind)["hbm_bytes"]
