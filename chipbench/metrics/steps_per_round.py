"""Lockstep micro-steps per sampling round (the program's counters)."""


def read(run):
    rounds = run.counters.get("rounds", 0)
    return run.counters["sampling_steps"] / rounds if rounds else None
