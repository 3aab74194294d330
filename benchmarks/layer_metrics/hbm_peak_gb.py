"""``peak_bytes_in_use`` of the fullest chip after the window, in GB
(1e9 bytes).  A backend that reports no memory statistics (the CPU
rehearsal) gives nothing."""

NAME, UNIT = "hbm_peak_gb", "GB"
LAYER, MOVES = "device", "vis_per_s"


def read(run):
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:run.cell.chips]]
    peaks = [p for p in peaks if p]
    return max(peaks) / 1e9 if peaks else None
