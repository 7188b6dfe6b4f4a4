"""Share of the traced window in which no operation ran on the device."""
from chipbench import trace


def read(run):
    return 100.0 * trace.idle_share(run.trace) if run.trace.devices else None
