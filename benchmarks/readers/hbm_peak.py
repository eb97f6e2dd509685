"""The allocator's peak bytes in use on the fullest chip over its
published capacity (``memory_stats()["peak_bytes_in_use"]``: live buffers,
not a running program's temporaries)."""
from .. import peaks


def read(ev):
    if ev.allocator_peak_bytes is None:
        return None
    return 100.0 * ev.allocator_peak_bytes / peaks.peaks_for(
        ev.device_kind)["hbm_bytes"]
