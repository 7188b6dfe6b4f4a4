"""The reductions of the program's spans and scopes (chipbench/
program_trace.py): the xplane metadata reader on bytes made by hand, and
idle time, scope unions, gap labels and span self time on intervals made by
hand."""
import sys

import numpy as np
import pytest

from chipbench import program_trace as pt
from chipbench import trace


# -- a protobuf writer for the few XSpace fields the reader takes ------------

def _varint(x: int) -> bytes:
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _len(num: int, payload: bytes) -> bytes:
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _int(num: int, x: int) -> bytes:
    return _varint(num << 3) + _varint(x)


def _str(num: int, s: str) -> bytes:
    return _len(num, s.encode())


def _plane(name, events, stats, lines=b""):
    out = _str(2, name) + _int(1, 7)
    if lines:
        out += _len(3, lines)
    for key, (ename, estats) in events.items():
        meta = _int(1, key) + _str(2, ename)
        for sid, kind, value in estats:
            meta += _len(5, _int(1, sid) + (
                _str(5, value) if kind == "str" else _int(7, value)))
        out += _len(4, _int(1, key) + _len(2, meta))
    for sid, sname in stats.items():
        out += _len(5, _int(1, sid) + _len(2, _int(1, sid)
                                            + _str(2, sname)))
    return out


def test_event_stat_reads_op_names_from_metadata(tmp_path):
    stats = {1: "tf_op", 2: "flops", 3: "jit(f)/while/body/rr.draw/add:"}
    tpu = _plane("/device:TPU:0", {
        10: ("%fusion.1 = f32[8] fusion()", [
            (2, "str", "9"),
            (1, "str", "jit(f)/while/body/rr.edges/gather:")]),
        11: ("%add.2 = f32[8] add()", [(1, "ref", 3)]),
        12: ("%copy.3 = f32[8] copy()", [(2, "str", "0")])},
        stats, lines=_str(2, "XLA Ops") + _len(4, b"\x08\x0a"))
    host = _plane("/host:CPU", {1: ("imm.round", [(1, "str", "x")])}, stats)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_len(1, host) + _len(1, tpu) + _str(4, "host0"))
    got = pt.event_stat(memoryview(path.read_bytes()), "tf_op",
                        r"/device:TPU:\d+$")
    assert got == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion()": "jit(f)/while/body/rr.edges/gather:",
        "%add.2 = f32[8] add()": "jit(f)/while/body/rr.draw/add:"}}


def _instr(name, op_name=None, calls=(), packed=True):
    out = _str(1, name)
    if op_name is not None:
        out += _len(7, _str(1, "gather") + _str(2, op_name))
    if calls and packed:
        out += _len(38, b"".join(_varint(c) for c in calls))
    else:
        out += b"".join(_int(38, c) for c in calls)
    return out


def _hlo_proto(append="rr.append"):
    comps = {
        1: [_instr("while.14", "jit(_queue_round)/while", [4]),
            _instr("while.15", None, [2, 3]),
            _instr("fusion.7", "jit(f)/while/body/rr.edges/gather:"),
            _instr("copy.3")],
        2: [_instr("dus.1", f"jit(f)/while/body/{append}/vmap()/"
                            "dynamic_update_slice", [5], packed=False)],
        3: [_instr("lt.1")],
        4: [_instr("a", "jit(f)/while/body/rr.edges/add"),
            _instr("b", "jit(f)/while/body/rr.draw/lt")],
        5: [_instr("param.1")]}
    module = _str(1, "jit__queue_round") + b"".join(
        _len(3, _str(1, f"c{cid}") + b"".join(_len(2, i) for i in ins)
             + _int(5, cid)) for cid, ins in comps.items())
    return _len(1, module)


def test_hlo_scopes_fill_in_loops_a_compiler_pass_made():
    got = pt.hlo_scopes(_hlo_proto())
    assert got == {"while.15": "rr.append", "fusion.7": "rr.edges",
                   "dus.1": "rr.append", "a": "rr.edges", "b": "rr.draw"}


def _xevent(md, offset_ps, duration_ps, stats=b""):
    return _int(1, md) + _int(2, offset_ps) + _int(3, duration_ps) + stats


def _line(name, events):
    return (_str(2, name) + _int(3, 1000)
            + b"".join(_len(4, e) for e in events))


def _recorded_layout(path, stored):
    """A TPU trace's planes, lines and metadata, at a few microseconds,
    written to ``path``; ``stored`` holds the metadata plane's HLO protos,
    one per program name."""
    us = 1_000_000                                   # ps
    ops = _line("XLA Ops", [_xevent(2, 0, 10 * us), _xevent(3, 1 * us, 2 * us),
                            _xevent(4, 4 * us, 1 * us),
                            _xevent(5, 6 * us, 1 * us)])
    mods = _line("XLA Modules", [_xevent(1, 0, 10 * us)])
    tpu = _plane("/device:TPU:0", {
        1: ("jit__queue_round(7)", []),
        2: ("%while.14 = (s32[]) while(%t), body=%b", [
            (1, "str", "jit(_queue_round)/while")]),
        3: ("%while.15 = (s32[]) while(%t.1), body=%b.1", []),
        4: ("%fusion.7 = s32[8] fusion(%p)", [
            (1, "str", "jit(f)/while/body/rr.edges/gather:")]),
        5: ("%copy.3 = s32[8] copy(%p)", [])},
        {1: "tf_op"}, lines=mods)
    tpu += _len(3, ops)
    host = _plane("/host:CPU", {1: ("imm.round", []), 2: ("other", [])},
                  {7: "round"}, lines=_line("python", [
                      _xevent(1, 0, 9 * us, _len(4, _int(1, 7) + _int(4, 3))),
                      _xevent(2, 0, 1 * us)]))
    meta = _plane("/host:metadata", {
        i + 1: (name, []) for i, name in enumerate(stored)}, {9: "Hlo Proto"})
    for i, (name, proto) in enumerate(stored.items()):
        meta += _len(4, _int(1, i + 1) + _len(2, _int(1, i + 1) + _str(
            2, name) + _len(5, _int(1, 9) + _len(6, proto))))
    path.write_bytes(_len(1, tpu) + _len(1, host) + _len(1, meta))
    return pt.load(str(path), trace.load(str(path), ()))


def test_load_reads_spans_and_scopes_from_a_recorded_layout(tmp_path):
    prog = _recorded_layout(tmp_path / "t.xplane.pb",
                            {"jit__queue_round(7)": _hlo_proto()})
    assert [(s[0], s[3]) for s in prog.spans] == [("imm.round",
                                                   {"round": 3})]
    assert len(prog.scopes) == 1
    assert set(prog.scopes[0]) == {"rr.append", "rr.edges"}
    # line start 1,000 ns; the loop runs 1-3 us into it, the gather 4-5 us
    np.testing.assert_allclose(prog.scopes[0]["rr.append"] * 1e9,
                               [[2000, 4000]])
    np.testing.assert_allclose(prog.scopes[0]["rr.edges"] * 1e9,
                               [[5000, 6000]])


def test_load_leaves_out_a_loop_that_two_programs_of_one_name_scope_apart(
        tmp_path):
    # the trace names a program without its fingerprint: where two stored
    # programs of that name put one loop in different scopes, it has none
    prog = _recorded_layout(tmp_path / "t.xplane.pb", {
        "jit__queue_round(7)": _hlo_proto(),
        "jit__queue_round(8)": _hlo_proto(append="rr.dedup")})
    assert set(prog.scopes[0]) == {"rr.edges"}
    prog = _recorded_layout(tmp_path / "u.xplane.pb", {
        "jit__queue_round(7)": _hlo_proto(),
        "jit__queue_round(8)": _hlo_proto()})
    assert set(prog.scopes[0]) == {"rr.append", "rr.edges"}


@pytest.mark.parametrize("name, scope", [
    ("jit(_queue_round)/jit(_sample_queue)/while/body/rr.edges/gather:",
     "rr.edges"),
    ("jit(_queue_round)/while/body/rr.append/vmap()/dynamic_update_slice",
     "rr.append"),
    ("jit(f)/rr.append/rr.visited_write/cond/scatter-add", "rr.visited_write"),
    ("jit(_queue_round)/while/cond/lt", None),
    ("jit(f)/arr.edges/gather", None),
    (None, None)])
def test_scope_of(name, scope):
    assert pt.scope_of(name) == scope


def _timeline(ops, mods=()):
    return trace.Timeline(
        name="/device:TPU:0", mod_names=[m[0] for m in mods],
        mod_t=np.asarray([m[1:] for m in mods], float).reshape(-1, 2),
        op_names=[o[0] for o in ops],
        op_t=np.asarray([o[1:] for o in ops], float).reshape(-1, 2))


def _iv(*rows):
    return np.asarray(rows, float).reshape(-1, 2)


def test_scoped_ops_nested_in_a_while_count_once():
    # a while op of the append scope holds its own body ops, and an op of
    # another scope
    prog = pt.Program(spans=[], scopes=[{
        "rr.append": _iv((0.0, 4.0), (1.0, 2.0), (2.5, 3.0), (3.5, 4.5)),
        "rr.edges": _iv((5.0, 6.0), (5.5, 6.5)),
        "rr.visited_test": _iv((1.2, 1.4)),
        "rr.visited_write": _iv((1.3, 1.6), (7.0, 7.5))}])
    tr = trace.Trace(devices=[_timeline([("while", 0.0, 8.0)])], spans=[],
                     window=(0.0, 10.0))
    got = pt.scope_seconds(tr, prog)
    assert got["rr.append"] == pytest.approx(4.5)
    assert got["rr.edges"] == pytest.approx(1.5)
    groups = pt.scope_seconds(tr, prog, {"v": list(pt.VISITED)})
    assert groups["v"] == pytest.approx(0.4 + 0.5)
    m = pt.metrics(tr, prog, {"sampling_steps": 3, "rounds": 1})
    assert m["sample_append_ms_per_step"] == pytest.approx(1500.0)
    assert m["sample_edges_ms_per_step"] == pytest.approx(500.0)
    assert m["sample_visited_ms_per_step"] == pytest.approx(300.0)
    assert pt.report(tr, prog, {"sampling_steps": 3})[
        "scope_ms_per_step"]["rr.append"] == pytest.approx(1500.0)


def _spans():
    return [("imm.solve", 0.5, 9.5, {"solve": 1}),
            ("imm.sample_until", 1.5, 4.0, {"theta": 8}),
            ("imm.round", 1.6, 3.9, {"round": 1}),
            ("imm.fetch", 2.1, 2.9, {"what": "shard_counts"}),
            ("imm.select", 5.0, 7.0, {"method": "auto"})]


def test_idle_inside_and_outside_spans():
    # busy 0-2, 3-5, 8-10; idle 2-3 (in sample_until), 5-7 (in select),
    # 7-8 (in imm.solve alone)
    d = _timeline([("a", 0.0, 2.0), ("b", 3.0, 5.0), ("c", 8.0, 10.0)])
    tr = trace.Trace(devices=[d], spans=[("solve", 0.0, 10.0)],
                     window=(0.0, 10.0))
    prog = pt.Program(spans=_spans())
    until = pt.span_intervals(prog, {"imm.sample_until"})
    assert pt.idle_inside_s(tr, until) == pytest.approx(1.0)
    assert pt.idle_inside_s(tr, pt.span_intervals(prog, {"imm.select"})) \
        == pytest.approx(2.0)
    assert pt.idle_inside_s(tr, pt.span_intervals(prog)) == pytest.approx(4.0)
    m = pt.metrics(tr, prog, {"sampling_steps": 0, "rounds": 2,
                              "selections": 1, "host_fetches": 7})
    assert m["round_sync_ms.solve"] == pytest.approx(500.0)
    assert m["select_sync_ms.solve"] == pytest.approx(2000.0)
    assert m["unattributed_idle_share.solve"] == pytest.approx(0.0)
    assert m["host_fetches_per_round"] == pytest.approx(3.5)
    # the idle second 7-8 leaves every span once imm.solve ends at 7.5
    prog.spans[0] = ("imm.solve", 0.5, 7.5, {"solve": 1})
    m = pt.metrics(tr, prog, {"rounds": 2, "selections": 1})
    assert m["unattributed_idle_share.solve"] == pytest.approx(5.0)
    assert m["unattributed_idle_share.solve"] <= 100 * trace.idle_share(tr)


def test_program_span_outranks_the_benchmark_solve_in_gap_labels():
    d = _timeline([("a", 0.0, 2.0), ("b", 3.0, 5.0), ("c", 7.0, 7.2),
                   ("d", 7.6, 9.8)])
    tr = trace.Trace(devices=[d], spans=[("solve", 0.0, 10.0)],
                     window=(0.0, 10.0))
    prog = pt.Program(spans=_spans())
    got = pt.idle_gaps(tr, prog)
    assert got == [["imm.select", pytest.approx(2.0)],        # 5 .. 7
                   ["imm.fetch", pytest.approx(1.0)],         # 2 .. 3
                   ["imm.solve", pytest.approx(0.4)],         # 7.2 .. 7.6
                   ["solve", pytest.approx(0.2)]]             # 9.8 .. 10
    # the benchmark's own labels stay as they were
    assert [g[0] for g in trace.idle_gaps(tr)] == ["solve"] * 4


def test_span_table_counts_self_time():
    prog = pt.Program(spans=_spans() + [("imm.fetch", 7.5, 8.0,
                                         {"what": "result"})])
    prog.spans.sort(key=lambda s: (s[1], -s[2]))
    got = pt.span_table(prog)
    assert got["imm.fetch"][0] == 2
    assert got["imm.fetch"][1] == pytest.approx(1300.0)
    assert got["imm.round"][2] == pytest.approx(2300.0 - 800.0)
    assert got["imm.sample_until"][2] == pytest.approx(2500.0 - 2300.0)
    assert got["imm.solve"][2] == pytest.approx(9000.0 - 2500.0 - 2000.0
                                                - 500.0)


def test_fetch_clock_places_each_fetch_between_two_modules():
    d = _timeline([("a", 0.0, 1.0)],
                  mods=[("jit__queue_round", 0.0, 2.0),
                        ("jit__shard_counts", 2.0, 2.8995),
                        ("jit_append_packed", 3.0, 3.5),
                        ("jit__shard_counts", 6.0, 6.1),
                        ("jit_append_packed", 6.1011, 6.2)])
    tr = trace.Trace(devices=[d], spans=[], window=(0.0, 10.0))
    prog = pt.Program(spans=_spans() + [
        ("imm.fetch", 5.9, 6.1004, {"what": "shard_counts"}),
        ("imm.fetch", 7.0, 7.1, {"what": "lb_frac"})])
    got = pt.fetch_clock_ms(tr, prog)
    assert [g[0] for g in got] == [pytest.approx(0.5), pytest.approx(0.4)]
    assert [g[1] for g in got] == [pytest.approx(100.0), pytest.approx(0.7)]


def test_a_program_without_spans_or_scopes_reports_nothing():
    d = _timeline([("a", 0.0, 2.0)])
    tr = trace.Trace(devices=[d], spans=[("solve", 0.0, 10.0)],
                     window=(0.0, 10.0))
    got = pt.metrics(tr, pt.Program(spans=[], scopes=[{}]),
                     {"sampling_steps": 10, "rounds": 2, "selections": 3})
    assert got == {}


def test_a_traced_cycle_on_the_cpu():
    # the tiny cell of the drill tests; on the CPU no TPU plane exists, so
    # only the host spans and the counter have something to read
    import jax
    from chipbench import spec
    from chipbench.tests._tiny import SOLVE
    cell = spec.cell(SOLVE)
    cell = cell._replace(config={**cell.config, "n_nodes": 3000})
    got = pt.trace_cycle(cell, 2 ** 31 + 5, 0.5, jax.devices()[:1])
    spans = got["spans"]
    assert spans["imm.solve"][0] == 1
    assert spans["imm.round"][0] == got["counters"]["rounds"]
    assert spans["imm.fetch"][0] == got["counters"]["host_fetches"]
    assert all(row[2] <= row[1] + 1e-9 for row in spans.values())
    assert got["metrics"]["host_fetches_per_round"] == pytest.approx(
        got["counters"]["host_fetches"] / got["counters"]["rounds"])
    assert got["scope_ms_per_step"] == {}
    assert got["accepted"]["steps_per_round"] > 0


def test_refuses_a_cpu(capsys, monkeypatch):
    # the run's own refusals: off the chip no result line, exit 2
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    saved = list(sys.path)
    try:
        code = pt.main(["--workload", "ba-r4-75k.solve", "--seed", "3",
                        "--seconds", "1"])
    finally:
        sys.path[:] = saved
    out = capsys.readouterr()
    assert code == 2
    assert "needs 1 TPU chip(s)" in out.err
    assert out.out == ""
