"""Device time of the selection programs per selection of a solve (each
LB-loop selection and the final one)."""


def read(run):
    t = run.layer_mean_s("selection")
    sel = run.counters.get("selections", 0)
    return 1e3 * t / sel if t > 0 and sel else None
