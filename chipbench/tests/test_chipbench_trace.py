"""The trace reduction: idle share, module-to-layer grouping and gap labels,
on intervals made by hand and on a small trace recorded on the CPU."""
import time

import numpy as np
import pytest

from chipbench import trace


def _timeline(mods, ops):
    return trace.Timeline(
        name="/device:TPU:0", mod_names=[m[0] for m in mods],
        mod_t=np.asarray([m[1:] for m in mods], float).reshape(-1, 2),
        op_names=[o[0] for o in ops],
        op_t=np.asarray([o[1:] for o in ops], float).reshape(-1, 2))


def test_union_merges_and_clips():
    iv = np.array([[0.0, 2.0], [1.0, 3.0], [5.0, 6.0], [9.0, 12.0]])
    np.testing.assert_allclose(trace.union(iv, 0.5, 10.0),
                               [[0.5, 3.0], [5.0, 6.0], [9.0, 10.0]])


def test_hand_made_trace():
    d = _timeline(
        mods=[("jit__queue_round", 0.0, 4.0), ("jit_fused", 5.0, 6.0),
              ("jit_other", 8.0, 9.0)],
        ops=[("while.1", 0.0, 4.0), ("scatter.2", 5.0, 6.0),
             ("all-reduce.3", 8.0, 8.5)])
    tr = trace.Trace(devices=[d], spans=[("solve", 0.0, 6.5),
                                         ("generate", 6.5, 10.0)],
                     window=(0.0, 10.0))
    assert trace.busy_s(tr) == pytest.approx(5.5)
    assert trace.idle_share(tr) == pytest.approx(0.45)
    table = [["queue_round", "sampling engine"], ["fused", "selection"]]
    assert trace.layer_seconds(tr, table) == [
        {"sampling engine": 4.0, "selection": 1.0}]
    gaps = trace.idle_gaps(tr)
    assert gaps == [["generate", pytest.approx(2.0)],       # 6 .. 8
                    ["generate", pytest.approx(1.5)],       # 8.5 .. 10
                    ["solve", pytest.approx(1.0)]]          # 4 .. 5
    assert trace.top_ops(tr)[0] == ["jit__queue_round/while.1",
                                    pytest.approx(4.0)]
    assert trace.short("%fusion.3 = f32[8]{0} fusion(%p.1)") == "%fusion.3"
    assert trace.short("jit_fused(1651500)") == "jit_fused"


def test_cpu_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def kernel(x):
        return jnp.sin(x) @ x

    x = jnp.ones((256, 256))
    kernel(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        with jax.profiler.TraceAnnotation("solve"):
            for _ in range(3):
                kernel(x).block_until_ready()
        with jax.profiler.TraceAnnotation("generate"):
            time.sleep(0.3)
        with jax.profiler.TraceAnnotation("solve"):
            kernel(x).block_until_ready()
    jax.profiler.stop_trace()
    # on the CPU the "device" is the XLA client's threads, the modules are
    # the dispatches on the Python thread
    tr = trace.load(str(tmp_path), ("solve", "generate"),
                    device_plane=r"/host:CPU$",
                    ops_line=r"tf_XLAPjRtCpuClient",
                    modules_line=r"python$",
                    skip_op=r"ThreadpoolListener|end: ")
    assert len(tr.devices) == 1
    assert tr.window_s >= 0.3
    assert {s[0] for s in tr.spans} == {"solve", "generate"}
    share = trace.idle_share(tr)
    assert 0.5 < share < 1.0          # the 0.3 s sleep is idle
    layers = trace.layer_seconds(tr, [[r"^PjitFunction$", "kern"]])
    assert layers[0].get("kern", 0.0) > 0.0
    assert set(layers[0]) == {"kern"}
    longest = trace.idle_gaps(tr)[0]
    assert longest[0] == "generate" and longest[1] >= 0.25
