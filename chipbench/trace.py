"""Reduce a JAX profiler trace to device busy time, layer times and labelled
idle gaps.

A device plane (``/device:TPU:<i>``) carries the compiled programs that ran
(line ``XLA Modules``) and their operations (line ``XLA Ops``).  The
benchmark's own host spans (``jax.profiler.TraceAnnotation``) sit on the
host plane; the span ``window`` marks the measured window.  Module names
are grouped into layers by the patterns of ``chipbench/layers.json``.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

WINDOW = "window"


@dataclass
class Timeline:
    """One device: modules and ops as (name, start_s, end_s) arrays."""
    name: str
    mod_names: list
    mod_t: np.ndarray          # (M, 2)
    op_names: list
    op_t: np.ndarray           # (O, 2)


@dataclass
class Trace:
    devices: list
    spans: list                # (name, start_s, end_s), benchmark's own
    window: tuple = field(default=(0.0, 0.0))

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9


def short(name: str) -> str:
    """An op's name without its HLO text (``%fusion.3 = f32[...] ...`` ->
    ``%fusion.3``), a module's without its fingerprint."""
    return name.split(" = ", 1)[0].split("(", 1)[0] if name.startswith(
        "%") else name.split("(", 1)[0]


def _arr(rows):
    return (np.asarray(rows, np.float64).reshape(-1, 2) if rows
            else np.zeros((0, 2)))


def load(path: str, span_names, *, device_plane=r"/device:TPU:\d+$",
         ops_line=r"XLA Ops$", modules_line=r"XLA Modules$",
         span_plane=r"/host:CPU$", skip_op=r"$^") -> Trace:
    """Read the ``.xplane.pb`` under ``path`` (a profiler log directory or
    the file).  The patterns pick the planes and lines; the defaults are a
    TPU's."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    data = ProfileData.from_file(path)
    devices, spans = [], []
    wanted = set(span_names) | {WINDOW}
    skip = re.compile(skip_op)
    names: dict = {}        # full event name -> short name, None to skip

    def keep(line):
        out_n, out_t = [], []
        for e in line.events:
            name = names.get(e.name, "")
            if name == "":
                name = names[e.name] = (None if skip.match(e.name)
                                        else short(e.name))
            if name is not None:
                out_n.append(name)
                out_t.append((e.start_ns, e.start_ns + e.duration_ns))
        return out_n, out_t

    for plane in data.planes:
        if re.match(span_plane, plane.name):
            for line in plane.lines:
                spans += [s for s in _events(line) if s[0] in wanted]
        if not re.match(device_plane, plane.name):
            continue
        mod_n, mod_t, op_n, op_t = [], [], [], []
        for line in plane.lines:
            if re.match(modules_line, line.name):
                n, t = keep(line)
                mod_n += n
                mod_t += t
            if re.match(ops_line, line.name):
                n, t = keep(line)
                op_n += n
                op_t += t
        devices.append(Timeline(name=plane.name, mod_names=mod_n,
                                mod_t=_arr(mod_t) * 1e-9, op_names=op_n,
                                op_t=_arr(op_t) * 1e-9))
    devices.sort(key=lambda d: d.name)
    win = [s for s in spans if s[0] == WINDOW]
    if win:
        window = (win[0][1], win[0][2])
    else:
        ends = [t for d in devices for t in (d.op_t.ravel(), d.mod_t.ravel())
                if t.size]
        allt = np.concatenate(ends) if ends else np.zeros(2)
        window = (float(allt.min()), float(allt.max()))
    return Trace(devices=devices,
                 spans=[s for s in spans if s[0] != WINDOW], window=window)


def union(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Disjoint, sorted union of (start, end) rows clipped to [lo, hi]."""
    if intervals.size == 0:
        return np.zeros((0, 2))
    iv = np.clip(intervals, lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if iv.size == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > reach[:-1]
    starts = iv[new, 0]
    last = np.append(np.flatnonzero(new)[1:] - 1, len(iv) - 1)
    return np.stack([starts, reach[last]], axis=1)


def _busy_intervals(d: Timeline, lo: float, hi: float) -> np.ndarray:
    return union(d.op_t if d.op_t.size else d.mod_t, lo, hi)


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    lo, hi = tr.window
    per = [np.diff(_busy_intervals(d, lo, hi), axis=1).sum()
           for d in tr.devices]
    return float(np.mean(per)) if per else 0.0


def idle_share(tr: Trace) -> float:
    return 1.0 - busy_s(tr) / tr.window_s


def layer_of(name: str, table) -> str | None:
    for pattern, layer in table:
        if re.search(pattern, name):
            return layer
    return None


def layer_seconds(tr: Trace, table) -> list:
    """Per device: {layer: seconds of its modules inside the window}."""
    lo, hi = tr.window
    out = []
    for d in tr.devices:
        acc: dict = {}
        if d.mod_t.size:
            dur = np.clip(d.mod_t[:, 1], lo, hi) - np.clip(d.mod_t[:, 0], lo,
                                                           hi)
            for name, s in zip(d.mod_names, dur):
                layer = layer_of(name, table)
                if layer is not None and s > 0:
                    acc[layer] = acc.get(layer, 0.0) + float(s)
        out.append(acc)
    return out


def idle_gaps(tr: Trace, top: int = 10) -> list:
    """The longest gaps on the first device with no operation running,
    each labelled by the innermost benchmark span open at its middle
    (``none`` where there is none)."""
    if not tr.devices:
        return []
    lo, hi = tr.window
    busy = _busy_intervals(tr.devices[0], lo, hi)
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    order = np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")[:top]
    out = []
    for a, b in gaps[order]:
        mid = 0.5 * (a + b)
        open_ = [s for s in tr.spans if s[1] <= mid <= s[2]]
        label = max(open_, key=lambda s: s[1])[0] if open_ else "none"
        out.append([label, float(b - a)])
    return out


def top_ops(tr: Trace, top: int = 10) -> list:
    """The device operations that took the most time, summed by name
    (``module/op``) and averaged over the devices."""
    lo, hi = tr.window
    acc: dict = {}
    for d in tr.devices:
        if not d.op_t.size:
            continue
        mods = np.argsort(d.mod_t[:, 0], kind="stable") if d.mod_t.size \
            else np.zeros(0, int)
        starts = d.mod_t[mods, 0] if mods.size else np.zeros(0)
        at = np.searchsorted(starts, d.op_t[:, 0], side="right") - 1
        for name, (a, b), j in zip(d.op_names, d.op_t, at):
            s = min(b, hi) - max(a, lo)
            if s > 0:
                key = (f"{d.mod_names[mods[j]]}/{name}" if j >= 0
                       else name)
                acc[key] = acc.get(key, 0.0) + s
    n = max(len(tr.devices), 1)
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[name, float(s / n)] for name, s in best]


def top_modules(tr: Trace, top: int = 10) -> list:
    lo, hi = tr.window
    acc: dict = {}
    for d in tr.devices:
        for name, (a, b) in zip(d.mod_names, d.mod_t):
            s = min(b, hi) - max(a, lo)
            if s > 0:
                acc[name] = acc.get(name, 0.0) + s
    n = max(len(tr.devices), 1)
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[name, float(s / n)] for name, s in best]
