"""Device time of the sampling-round programs per lockstep micro-step."""


def read(run):
    t = run.layer_mean_s("sampling engine")
    steps = run.counters.get("sampling_steps", 0)
    return 1e3 * t / steps if t > 0 and steps > 0 else None
