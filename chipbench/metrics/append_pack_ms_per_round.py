"""Device time of the row-offset pool append (``jit_append_rows``
modules) per round; None where the program has no such module."""
import re

APPEND_ROWS = re.compile(r"^jit_append_rows")


def read(run):
    lo, hi = run.trace.window
    per = [sum(max(min(b, hi) - max(a, lo), 0.0)
               for name, (a, b) in zip(d.mod_names, d.mod_t)
               if APPEND_ROWS.match(name))
           for d in run.trace.devices]
    t = sum(per) / len(per) if per else 0.0
    rounds = run.counters.get("rounds", 0)
    return 1e3 * t / rounds if t > 0 and rounds else None
