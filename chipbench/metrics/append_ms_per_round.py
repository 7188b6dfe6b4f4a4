"""Device time of the pool-store programs (append, growth) per round."""


def read(run):
    t = run.layer_mean_s("pool store")
    rounds = run.counters.get("rounds", 0)
    return 1e3 * t / rounds if t > 0 and rounds else None
