#!/usr/bin/env python3
"""The program's own spans and named scopes in a profiler trace.

The program opens host spans named ``imm.*`` around its solve loop
(``src/repro/core/spans.py``); they land on the trace's host plane, on the
device events' clock, with their arguments as stats.  The queue sampler's
step puts each of its ops in an ``rr.*`` named scope
(``src/repro/core/rrset.py``); the scope reaches the op's metadata, which a
TPU op event carries as its ``tf_op`` stat (``jit(_queue_round)/.../
rr.edges/gather``).  A loop or fusion that a compiler pass made has no
source name; its scope comes from the HLO that the trace stores for its
module, as the one scope of the instructions it calls.  jax's
``ProfileData`` leaves event metadata out, so :func:`event_stat` reads it
from the ``.xplane.pb`` with a protobuf wire reader that skips the event
lines.

The reductions take a :class:`chipbench.trace.Trace` (device busy time, the
benchmark's spans, the window) and a :class:`Program` read from the same
trace.  Where ops nest (a while op and its body), a scope's time is the
union of its ops' intervals.  Run as a script, it sets up a cell, traces
its first cycle as ``chipbench/run.py --trace 1`` does, and prints one JSON
line with the cell's per-layer metrics and these reductions:

    python3 chipbench/program_trace.py --workload ba-r4-75k.solve \
        --seed 7 --seconds 10
"""
from __future__ import annotations

import dataclasses
import os
import re
import sys
from dataclasses import dataclass, field

import numpy as np

if __name__ == "__main__":          # as a script: the checkout's packages
    sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))]

from chipbench import trace  # noqa: E402

PREFIX = "imm."
SCOPE = re.compile(r"(?:^|/)(rr\.[A-Za-z_]+)(?=/|:|$)")
OP_NAME_STAT = "tf_op"
SPAN_PLANE = r"/host:CPU$"           # the host plane, as chipbench.trace's
HLO_PLANE = r"/host:metadata$"       # where the profiler stores the HLO


# -- event metadata and stored HLO: a protobuf wire reader ------------------
# XSpace.planes=1; XPlane.name=2, .event_metadata=4 and .stat_metadata=5
# (map entries: key=1, value=2); XEventMetadata.name=2, .stats=5;
# XStatMetadata.name=2; XStat.metadata_id=1, .str_value=5, .bytes_value=6,
# .ref_value=7.  HloProto.hlo_module=1; HloModuleProto.computations=3;
# HloComputationProto.instructions=2, .id=5; HloInstructionProto.name=1,
# .metadata=7 (OpMetadata.op_name=2), .called_computation_ids=38.

def _varint(buf, i: int):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field, value) of one message in ``buf[lo:hi]``: a varint's value,
    or a length-delimited field's (start, end)."""
    i = lo
    while i < hi:
        tag, i = _varint(buf, i)
        num, wire = tag >> 3, tag & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wire in (1, 5):
            v = None
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, v


def _str(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _sub(buf, span, want: dict) -> dict:
    """The fields of one sub-message named in ``want`` (number -> key)."""
    out: dict = {}
    for num, v in _fields(buf, *span):
        if num in want:
            out.setdefault(want[num], []).append(v)
    return out


def _ints(buf, values) -> list:
    """A repeated integer field, packed or not."""
    out = []
    for v in values:
        if isinstance(v, tuple):
            i = v[0]
            while i < v[1]:
                x, i = _varint(buf, i)
                out.append(x)
        else:
            out.append(v)
    return out


def event_stat(buf, stat: str, plane: str) -> dict:
    """{plane name: {event name: the stat's value}} for each plane of the
    serialized XSpace ``buf`` whose name matches ``plane``, from its event
    metadata; a string stat's value is a ``str``, a bytes stat's a
    ``memoryview``."""
    out = {}
    for num, p in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        parts = _sub(buf, p, {2: "name", 4: "events", 5: "stats"})
        name = _str(buf, parts["name"][0]) if "name" in parts else ""
        if not re.match(plane, name):
            continue
        stat_name = {}
        for e in parts.get("stats", []):
            entry = _sub(buf, e, {1: "key", 2: "value"})
            meta = _sub(buf, entry["value"][0], {2: "name"})
            stat_name[entry["key"][0]] = _str(buf, meta["name"][0])
        found = {}
        for e in parts.get("events", []):
            entry = _sub(buf, e, {2: "value"})
            meta = _sub(buf, entry["value"][0], {2: "name", 5: "stats"})
            for st in meta.get("stats", []):
                st = _sub(buf, st, {1: "id", 5: "str", 6: "bytes", 7: "ref"})
                if stat_name.get(st["id"][0]) != stat:
                    continue
                if "str" in st:
                    value = _str(buf, st["str"][0])
                elif "bytes" in st:
                    a, b = st["bytes"][0]
                    value = buf[a:b]
                else:
                    value = stat_name.get(st.get("ref", [None])[0], "")
                found[_str(buf, meta["name"][0])] = value
        out[name] = found
    return out


def scope_of(op_name: str) -> str | None:
    """The innermost ``rr.*`` scope of an op's source name."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else None


def hlo_scopes(hlo_proto) -> dict:
    """{instruction name: scope} for every instruction of a serialized
    ``HloProto`` that has one: its own op name's scope or, where that has
    none (a loop or fusion that a compiler pass made), the one scope that
    the instructions of the computations it calls carry."""
    buf = memoryview(hlo_proto)
    module = _sub(buf, (0, len(buf)), {1: "m"}).get("m", [])
    comps, instrs = {}, []
    for c in (_sub(buf, module[0], {3: "c"}).get("c", []) if module
              else []):
        parts = _sub(buf, c, {2: "i", 5: "id"})
        ids = comps.setdefault(parts.get("id", [0])[0], [])
        for i in parts.get("i", []):
            f = _sub(buf, i, {1: "name", 7: "meta", 38: "calls"})
            meta = _sub(buf, f["meta"][0], {2: "op"}) if "meta" in f else {}
            own = scope_of(_str(buf, meta["op"][0])) if "op" in meta else None
            instrs.append((_str(buf, f["name"][0]), own,
                           _ints(buf, f.get("calls", []))))
            ids.append(len(instrs) - 1)
    memo: dict = {}

    def within(cid, seen=()) -> frozenset:
        """The scopes that computation ``cid`` and its callees carry."""
        if cid not in memo:
            got = set()
            for j in comps.get(cid, []):
                _, own, calls = instrs[j]
                if own is not None:
                    got.add(own)
                for c in calls:
                    if c not in seen:
                        got |= within(c, seen + (cid,))
            memo[cid] = frozenset(got)
        return memo[cid]

    out = {}
    for name, own, calls in instrs:
        if own is None and calls:
            inner = frozenset().union(*(within(c) for c in calls))
            own = next(iter(inner)) if len(inner) == 1 else None
        if own is not None:
            out[name] = own
    return out


# -- the program's part of a trace -------------------------------------------

@dataclass
class Program:
    spans: list                    # (name, start_s, end_s, args) of imm.*
    scopes: list = field(default_factory=list)   # per device: {scope: (K, 2)}


def load(path: str, tr: trace.Trace) -> Program:
    """The ``imm.*`` spans with their arguments, and the scoped intervals of
    each device's ops in ``tr`` (:func:`chipbench.trace.load` of the same
    trace), from the trace under ``path`` (a profiler log directory or its
    ``.xplane.pb``).  An op's scope comes from its own metadata
    (``tf_op``), else from the HLO that the trace stores for its module."""
    from jax.profiler import ProfileData
    path = _xplane_file(path)
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    op_names = {plane: {trace.short(k): v for k, v in ops.items()}
                for plane, ops in event_stat(buf, OP_NAME_STAT,
                                             r"/device:").items()}
    # module name (the Trace's, without its fingerprint) -> {instruction:
    # scope}; an instruction that two programs of one name scope apart is
    # left out
    stored: dict = {}
    for hlo in event_stat(buf, "Hlo Proto", HLO_PLANE).values():
        for m, v in hlo.items():
            got = stored.setdefault(trace.short(m), {})
            for inst, scope in hlo_scopes(v).items():
                got[inst] = scope if got.get(inst, scope) == scope else None
    del buf
    spans = [(e.name, e.start_ns * 1e-9,
              (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if re.match(SPAN_PLANE, plane.name)
             for line in plane.lines for e in line.events
             if e.name.startswith(PREFIX)]
    scopes = []
    for d in tr.devices:
        names = op_names.get(d.name, {})
        order = np.argsort(d.mod_t[:, 0], kind="stable") if d.mod_t.size \
            else np.zeros(0, int)
        at = np.searchsorted(d.mod_t[order, 0] if order.size else
                             np.zeros(0), d.op_t[:, 0], side="right") - 1
        cache: dict = {}
        acc: dict = {}
        for name, iv, j in zip(d.op_names, d.op_t, at):
            module = d.mod_names[order[j]] if j >= 0 else ""
            s = cache.get((name, module), "")
            if s == "":
                s = cache[(name, module)] = (
                    scope_of(names.get(name))
                    or stored.get(module, {}).get(name.lstrip("%")))
            if s is not None:
                acc.setdefault(s, []).append(iv)
        scopes.append({k: np.asarray(v, np.float64).reshape(-1, 2)
                       for k, v in acc.items()})
    spans.sort(key=lambda s: (s[1], -s[2]))
    return Program(spans=spans, scopes=scopes)


def _xplane_file(path: str) -> str:
    """``path``'s ``.xplane.pb``: where :func:`chipbench.trace.load`
    finds it."""
    import glob
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        return files[-1]
    return path


# -- reductions ---------------------------------------------------------------

def covered_s(iv: np.ndarray, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that the union of ``iv`` covers."""
    return float(np.diff(trace.union(iv, lo, hi), axis=1).sum())


def span_intervals(prog: Program, names=None) -> np.ndarray:
    """(K, 2) intervals of the spans named in ``names`` (all if None)."""
    rows = [(a, b) for n, a, b, _ in prog.spans
            if names is None or n in names]
    return np.asarray(rows, np.float64).reshape(-1, 2)


def idle_inside_s(tr: trace.Trace, iv: np.ndarray) -> float:
    """Seconds in which the device is idle and ``iv`` covers the time,
    averaged over the devices: |busy ∪ iv| − |busy| inside the window."""
    lo, hi = tr.window
    per = []
    for d in tr.devices:
        busy = trace._busy_intervals(d, lo, hi)
        per.append(covered_s(np.concatenate([busy, iv.reshape(-1, 2)]), lo,
                             hi) - covered_s(busy, lo, hi))
    return float(np.mean(per)) if per else 0.0


def scope_seconds(tr: trace.Trace, prog: Program, groups=None) -> dict:
    """{scope: device seconds inside the window}, each scope (or each
    group of scopes, ``{name: [scopes]}``) the union of its ops' intervals,
    averaged over the devices."""
    lo, hi = tr.window
    if groups is None:
        groups = {s: [s] for d in prog.scopes for s in d}
    out = {}
    for name, members in groups.items():
        per = [covered_s(np.concatenate([d.get(s, np.zeros((0, 2)))
                                         for s in members]), lo, hi)
               for d in prog.scopes]
        out[name] = float(np.mean(per)) if per else 0.0
    return out


def span_table(prog: Program) -> dict:
    """{span name: [count, total ms, self ms]}: self time is a span's
    duration less the time its child spans cover."""
    child = [0.0] * len(prog.spans)
    stack: list = []
    for i, (_, a, b, _) in enumerate(prog.spans):
        while stack and prog.spans[stack[-1]][2] <= a:
            stack.pop()
        if stack and prog.spans[stack[-1]][2] >= b:
            child[stack[-1]] += b - a
        stack.append(i)
    out: dict = {}
    for (name, a, b, _), c in zip(prog.spans, child):
        row = out.setdefault(name, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += 1e3 * (b - a)
        row[2] += 1e3 * (b - a - c)
    return out


def idle_gaps(tr: trace.Trace, prog: Program, top: int = 10) -> list:
    """:func:`chipbench.trace.idle_gaps` with the program's spans beside
    the benchmark's: they nest inside the benchmark's ``solve``, so the
    innermost-span rule names a gap by the program span open there."""
    return trace.idle_gaps(dataclasses.replace(
        tr, spans=tr.spans + [s[:3] for s in prog.spans]), top)


def fetch_clock_ms(tr: trace.Trace, prog: Program) -> list:
    """For each ``imm.fetch`` span of the shard counts, on the first device:
    ``[lag, lead]`` in ms.  ``lag`` is the span's end less the end of the
    device module it waits for (the one whose end lies nearest); ``lead``
    is the start of the next module less the span's end, a module the host
    can only dispatch once the fetch returns.  On one clock both are
    positive."""
    if not tr.devices:
        return []
    d = tr.devices[0]
    order = np.argsort(d.mod_t[:, 0], kind="stable") if d.mod_t.size \
        else np.zeros(0, int)
    mod_t = d.mod_t[order] if order.size else np.zeros((0, 2))
    mine = np.asarray([d.mod_names[i] == "jit__shard_counts"
                       for i in order], bool)
    ends = mod_t[mine, 1] if mine.size else np.zeros(0)
    out = []
    for name, _, b, args in prog.spans:
        if name != "imm.fetch" or args.get("what") != "shard_counts" or \
                not ends.size:
            continue
        j = np.searchsorted(ends, b)
        near = ends[max(j - 1, 0):j + 1]
        end = near[np.argmin(np.abs(near - b))]
        later = mod_t[mod_t[:, 0] >= end, 0]
        out.append([1e3 * (b - end),
                    1e3 * (later.min() - b) if later.size else None])
    return out


VISITED = ("rr.visited_test", "rr.visited_write")


def metrics(tr: trace.Trace, prog: Program, counters: dict) -> dict:
    """The per-layer metrics that read the program's spans, scopes and
    ``host_fetches`` counter; a metric with nothing to read is left out."""
    steps = counters.get("sampling_steps", 0)
    rounds = counters.get("rounds", 0)
    sel = counters.get("selections", 0)
    scoped = scope_seconds(tr, prog, {
        "sample_edges_ms_per_step": ["rr.edges"],
        "sample_visited_ms_per_step": list(VISITED),
        "sample_append_ms_per_step": ["rr.append"]})
    out = {k: 1e3 * s / steps for k, s in scoped.items() if s > 0 and steps}
    if prog.spans and tr.devices:
        if rounds:
            out["round_sync_ms.solve"] = 1e3 * idle_inside_s(
                tr, span_intervals(prog, {"imm.sample_until"})) / rounds
        if sel:
            out["select_sync_ms.solve"] = 1e3 * idle_inside_s(
                tr, span_intervals(prog, {"imm.select"})) / sel
        idle = tr.window_s - trace.busy_s(tr)
        out["unattributed_idle_share.solve"] = 100.0 * (
            idle - idle_inside_s(tr, span_intervals(prog))) / tr.window_s
    if counters.get("host_fetches") is not None and rounds:
        out["host_fetches_per_round"] = counters["host_fetches"] / rounds
    return out


def report(tr: trace.Trace, prog: Program, counters: dict) -> dict:
    """What the operator reads beside the metrics: every scope's ms per
    step, every span's count, total and self ms, the relabelled idle gaps
    and where each shard-count fetch ends between its device module and
    the next."""
    steps = max(counters.get("sampling_steps", 0), 1)
    return {
        "scope_ms_per_step": {k: 1e3 * s / steps for k, s in
                              sorted(scope_seconds(tr, prog).items())},
        "spans": span_table(prog),
        "idle_gaps": idle_gaps(tr, prog),
        "fetch_clock_ms": fetch_clock_ms(tr, prog)}


# -- as a script --------------------------------------------------------------

def trace_cycle(cell, seed: int, seconds: float, devices) -> dict:
    """Set up ``cell``, run its window with the profiler over the first
    cycle (as ``chipbench/run.py --trace 1`` does), and reduce the trace:
    the cell's accepted per-layer metrics beside this module's."""
    import contextlib
    import importlib
    import shutil
    import tempfile
    import time
    import jax
    from chipbench import run, spec
    kind = importlib.import_module(f"chipbench.{cell.traffic['kind']}")
    state = kind.setup(cell, seed, devices)
    tracedir = tempfile.mkdtemp(prefix="chipbench-trace-")

    @contextlib.contextmanager
    def tracer(on: bool):
        if not on:
            yield
            return
        jax.profiler.start_trace(tracedir)
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()

    window = kind.window(state, seconds, tracer)
    counters = dict(window["counters"])
    stats = getattr(state.solves[0][1], "stats", None)
    if getattr(stats, "host_fetches", None) is not None:
        counters["host_fetches"] = stats.host_fetches
    t_read = time.perf_counter()
    tr = trace.load(tracedir, kind.SPANS)
    prog = load(tracedir, tr)
    t_read = time.perf_counter() - t_read
    shutil.rmtree(tracedir, ignore_errors=True)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    r = run.Run(cell=cell, window={"counters": counters}, device=device,
                trace=tr, layers=spec.layers())
    # sample_roofline needs the bytes that the check counts: left out
    accepted = {m["name"]: spec.reader(m["name"])(r) for m in cell.per_layer
                if m["name"] != "sample_roofline"}
    return {"workload": cell.name, "seed": seed, "device": device,
            "read_s": t_read,
            "window_s": tr.window_s, "busy_s": trace.busy_s(tr),
            "counters": counters, "accepted": accepted,
            "metrics": metrics(tr, prog, counters),
            "benchmark_idle_gaps": trace.idle_gaps(tr),
            **report(tr, prog, counters)}


def main(argv=None) -> int:
    """Refuses, as ``chipbench/run.py`` does (exit 2, no result), a host
    without the program's sources, without a TPU or with fewer chips than
    the cell asks for, and Pallas kernels that resolve to interpret mode:
    off the chip the device times would read as a TPU's."""
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from chipbench import run
    run.prepare_process()
    try:
        import repro  # noqa: F401
        from chipbench import spec
        cell = spec.cell(args.workload)
    except (ImportError, OSError, KeyError) as e:
        print(f"program_trace: cannot load the program or the cell: {e}",
              file=sys.stderr)
        return 2
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"program_trace: no accelerator: {e}", file=sys.stderr)
        return 2
    found = {"platform": devices[0].platform, "count": len(devices)}
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"program_trace: {args.workload} needs {cell.chips} TPU "
              f"chip(s), JAX found {found}", file=sys.stderr)
        return 2
    from repro.kernels import ops
    if ops.resolve_interpret():
        print("program_trace: Pallas kernels resolve to interpret mode",
              file=sys.stderr)
        return 2
    out = trace_cycle(cell, args.seed, args.seconds, devices[:cell.chips])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
