"""Share of the HBM roofline that the sampling programs reach: the least
time the reverse IC BFS of the window's RR sets needs at the chip's peak
bandwidth (chipbench/roofline.py counts the bytes), over the device time
of the sampling programs."""


def read(run):
    t = run.layer_mean_s("sampling engine")
    nbytes = run.counters.get("sample_bytes", 0)
    if t <= 0 or nbytes <= 0:
        return None
    least = nbytes / (run.peaks["hbm_bytes_per_s"] * run.device["count"])
    return 100.0 * least / t
