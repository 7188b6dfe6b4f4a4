"""The reader of ``append_pack_ms_per_round``: the row append's device time
per round from a hand-made trace, and nothing where the program has no
``jit_append_rows`` module."""
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import spec, trace


def _run(mods, rounds):
    d = trace.Timeline(
        name="/device:TPU:0", mod_names=[m[0] for m in mods],
        mod_t=np.asarray([m[1:] for m in mods], float).reshape(-1, 2),
        op_names=[], op_t=np.zeros((0, 2)))
    tr = trace.Trace(devices=[d], spans=[], window=(0.0, 10.0))
    return SimpleNamespace(trace=tr, counters={"rounds": rounds})


def test_reads_the_row_append_per_round():
    read = spec.reader("append_pack_ms_per_round")
    run = _run([("jit__queue_round", 0.0, 2.0),
                ("jit_append_rows", 2.0, 2.004),
                ("jit__shard_counts", 2.004, 2.005),
                ("jit_append_rows", 9.999, 10.5)], rounds=2)
    assert read(run) == pytest.approx(1e3 * (0.004 + 0.001) / 2)


def test_nothing_without_the_row_append():
    read = spec.reader("append_pack_ms_per_round")
    assert read(_run([("jit_append_packed", 2.0, 2.03)], rounds=4)) is None
    assert read(_run([("jit_append_rows", 2.0, 2.03)], rounds=0)) is None
