"""The largest value of a steplog byte count over the window's serving
steps, as a share of the chip's published memory: the compiled step's
temporaries (``program_temp_bytes``), which the allocator's peak leaves
out.  None where the records lack the field, where the program had no
analysis to offer (0), or for a device without published peaks."""
from .. import peaks
from .steplog_phase import per_step


def read(ev, field="program_temp_bytes"):
    xs = per_step(ev, [field])
    if not xs or max(xs) <= 0:
        return None
    try:
        capacity = peaks.peaks_for(ev.device_kind)["hbm_bytes"]
    except KeyError:
        return None
    return 100.0 * max(xs) / capacity
