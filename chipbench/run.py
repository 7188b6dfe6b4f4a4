#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload ba-r4-75k.solve --seed 7 \
        --seconds 40 --trace 0

Loads the cell named in ``BENCHMARK.json``, builds its graph, and its
traffic from ``--seed``, warms up every program the window uses (set-up), measures
for ``--seconds``, then checks what the window produced against the plain
references of ``chipbench/reference.py``.  The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the end-to-end metrics with ``--trace 0``, the per-layer ones with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checked``: each number compared, with its limit.  Earlier lines report
compilation and memory.  The numbers compared are also the last lines of
standard error.

Exits 2 with no result when the program's sources are missing, when JAX
finds no TPU, or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def prepare_process() -> None:
    """For a process that runs cells, before JAX is imported: the checkout's
    packages on the import path, and JAX's persistent compile cache at a
    fixed path inside the checkout."""
    # run as a script, this directory leads the import path: keep its
    # module names (trace, spec, ...) from shadowing others
    sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
        p for p in sys.path if os.path.abspath(p or ".") != HERE]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(HERE, ".cache",
                                                           "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


class CompileWatch:
    """Compile seconds and count, and persistent-cache hits and misses,
    from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.secs, self.count, self.hits, self.misses = 0.0, 0, 0, 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
            if event.startswith("/jax/core/compile/"):
                self.secs += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snap(self) -> dict:
        return {"compile_s": self.secs, "compiles": self.count,
                "cache_hits": self.hits, "cache_misses": self.misses}

    def since(self, before: dict) -> dict:
        now = self.snap()
        return {k: now[k] - before[k] for k in now}


def emit(doc: dict) -> None:
    print(json.dumps(doc), flush=True)


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def execute(cell, seed: int, seconds: float, trace: bool, devices, *,
            t0: float, drill=None) -> dict:
    """Set up, measure and check one cell; returns the result object.
    ``drill`` (``chipbench/drills.py``: a control or a planted fault, never
    used by a benchmark run) may break the state after set-up and alter
    answers before they are judged."""
    import importlib
    import jax
    from chipbench import spec
    from chipbench import trace as tr_mod
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    kind = importlib.import_module(f"chipbench.{cell.traffic['kind']}")
    watch = CompileWatch()
    t_kind = time.perf_counter()
    state = kind.setup(cell, seed, devices)
    if drill is not None and drill.after_setup is not None:
        drill.after_setup(state)
    setup_s = time.perf_counter() - t0
    emit({"phase": "setup", "setup_s": setup_s,
          "before_cell_s": t_kind - t0, **state.phases, **watch.snap()})
    before = watch.snap()
    tracedir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None

    @contextlib.contextmanager
    def tracer(on: bool):
        """Profile the block when this is a traced run and ``on``; the
        span ``window`` marks what was traced."""
        if not (trace and on):
            yield
            return
        jax.profiler.start_trace(tracedir)
        try:
            with jax.profiler.TraceAnnotation(tr_mod.WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()

    window = kind.window(state, seconds, tracer)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes(devices)}
    emit({"phase": "window", "in_window": watch.since(before),
          "memory_peak_bytes": device["memory_peak_bytes"],
          **window["report"]})
    checked = kind.check(state, window,
                         alter=None if drill is None else drill.alter)
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checked.values()),
              "attempted": window["attempted"], "failed": window["failed"]}
    if trace:
        t_read = time.perf_counter()
        run = Run(cell=cell, window=window, device=device,
                  trace=tr_mod.load(tracedir, kind.SPANS),
                  layers=spec.layers())
        shutil.rmtree(tracedir, ignore_errors=True)
        t_read = time.perf_counter() - t_read
        metrics = {}
        for m in cell.per_layer:
            value = spec.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = tr_mod.busy_s(run.trace)
        device["window_s"] = run.trace.window_s
        breakdown = {"device_ops": tr_mod.top_ops(run.trace),
                     "idle_gaps": tr_mod.idle_gaps(run.trace)}
        emit({"phase": "trace", "read_s": t_read,
              "modules": tr_mod.top_modules(run.trace),
              "layer_s": run.layer_s, "counters": window["counters"]})
    else:
        values = dict(window["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result.update(metrics=metrics, device=device)
    if trace:
        result["breakdown"] = breakdown
    for name, c in checked.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    result["checked"] = checked
    return result


class Run:
    """What a per-layer metric reader sees of one traced run."""

    def __init__(self, *, cell, window, device, trace, layers):
        from chipbench import trace as tr_mod
        self.cell, self.device, self.trace = cell, device, trace
        self.counters = window["counters"]
        self.layers = layers
        self.layer_s = tr_mod.layer_seconds(trace, layers["modules"])

    @property
    def peaks(self) -> dict:
        from chipbench import spec
        return spec.peaks(self.device["kind"])

    def layer_mean_s(self, layer: str) -> float:
        per = [d.get(layer, 0.0) for d in self.layer_s]
        return sum(per) / len(per) if per else 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    prepare_process()
    try:
        import repro  # noqa: F401
        from chipbench import spec
        cell = spec.cell(args.workload)
    except (ImportError, OSError, KeyError) as e:
        print(f"chipbench: cannot load the program or the cell: {e}",
              file=sys.stderr)
        return 2
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chipbench: no accelerator: {e}", file=sys.stderr)
        return 2
    found = {"platform": devices[0].platform, "count": len(devices)}
    if devices[0].platform != "tpu":
        print(f"chipbench: needs a TPU, JAX found {found}", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} chips, JAX "
              f"found {found}", file=sys.stderr)
        return 2
    from repro.kernels import ops
    if ops.resolve_interpret():
        print("chipbench: Pallas kernels resolve to interpret mode",
              file=sys.stderr)
        return 2
    result = execute(cell, args.seed, args.seconds, bool(args.trace),
                     devices[:cell.chips], t0=T0)
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
