"""Ahead-of-time compiles for a described TPU v5e: no chip is needed.

Interpret mode (every other test) cannot see what the TPU compiler refuses:
unaligned blocks, unsupported casts and reductions, layouts Mosaic cannot
infer, VMEM overflows.  These tests lower every Pallas kernel on a
user-reachable path with ``interpret=False`` at the node widths of the
``im_paper`` stand-ins (epinions-like n=75,879 and pokec-like
n=1,632,803), plus the fused selection and sketch-sweep ``shard_map``
programs on a 1-chip and a 4-chip mesh and the variant selection scan on
one chip, and compile them for ``v5e:2x2``.  The
queue sampler's chunk pack is compiled at the benchmark's lane count to
keep data-dependent gathers and scatters out of it, and one sampling
round and the pool append of its output at pokec width, to bound their
memory and to keep the round's edge fetch to whole-row gathers.

The topology is described inside a module fixture only: the TPU library
may be loaded by one process at a time, so nothing here touches it while
modules are imported or tests are collected.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import bitset, ops
from repro.kernels import sketch as ksketch

WIDTHS = {"epinions": 75_879, "pokec": 1_632_803}   # configs/im_paper.py
SKETCH_K = 1024                                       # store default


def _node_words(n):
    """A bit matrix's node-word width: ceil(n/32) in whole 128-word tiles."""
    return -(-n // (32 * 128)) * 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_compiles(topo):
    """Compiled kernels, no persistent cache: a described chip's programs
    cannot be read back from the cache, so writing them would only make
    later compiles warn.  The backend here is the CPU, so the kernel
    dispatch is steered to Mosaic explicitly."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was_cache = jax.config.jax_enable_compilation_cache
    was_interpret = ops.INTERPRET
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    ops.INTERPRET = False
    yield
    ops.INTERPRET = was_interpret
    jax.config.update("jax_enable_compilation_cache", was_cache)
    cc.reset_cache()


def _compile(fn, *args, **static):
    compiled = jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_occur_kernels_compile(width, one_chip, chip_compiles):
    w = _node_words(WIDTHS[width])
    rows = 4096
    words = _sds((rows, w), jnp.uint32, one_chip)
    mask = _sds((rows,), jnp.bool_, one_chip)
    c = _compile(lambda m, k: bitset.occur_from_bitset_masked(
        m, k, interpret=False), words, mask)
    # the word matrix reaches the kernel in place: no relayout copy of it
    assert c.memory_analysis().temp_size_in_bytes < rows * w * 4 // 8
    _compile(lambda m: bitset.occur_from_bitset(m, interpret=False), words)


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_union_popcount_kernel_compiles(width, one_chip, chip_compiles):
    rows = WIDTHS[width] + 1
    c = _compile(
        lambda s, c: ksketch.sketch_union_popcount(s, c, interpret=False),
        _sds((rows, SKETCH_K // 32), jnp.uint32, one_chip),
        _sds((SKETCH_K // 32,), jnp.uint32, one_chip))
    assert c.memory_analysis().temp_size_in_bytes < rows * SKETCH_K // 32


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_selection_programs_compile(width, chips, topo, chip_compiles):
    """The fused Alg. 7 scan and the sketch sweep as the solver runs them:
    one ``shard_map`` over a ``samples`` mesh, pool buffers sharded per
    chip, at a pool that fills a realistic share of 16 GB."""
    from repro.core.coverage import _mesh_select_fns
    n = WIDTHS[width]
    mesh = Mesh(np.asarray(topo.devices[:chips]), ("samples",))
    buf = NamedSharding(mesh, P("samples", None))
    vec = NamedSharding(mesh, P("samples"))
    b3 = NamedSharding(mesh, P("samples", None, None))
    fns = _mesh_select_fns(mesh)

    rows, elems = 1 << 20, 1 << 22                  # per shard
    flat = _sds((chips, elems), jnp.int32, buf)
    valid = _sds((chips, elems), jnp.bool_, buf)
    nrr = _sds((chips,), jnp.int32, vec)
    c = fns.fused.lower(flat, flat, valid, nrr, num_rows=rows, n=n,
                        k=50).compile()
    assert c.memory_analysis().temp_size_in_bytes < 2 << 30

    sk = _sds((chips, -(-(n + 1) // chips) * chips, SKETCH_K // 32),
              jnp.uint32, b3)
    cov = _sds((chips, SKETCH_K // 32), jnp.uint32, buf)
    c = fns.sweep.lower(sk, cov, stripe=sk.shape[1] // chips,
                        interpret=False).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_variant_selection_programs_compile(width, topo, chip_compiles):
    """The variant scan (``program``: candidate mask, costs, group
    budgets), unweighted and row-weighted, as the solver runs it on one
    chip at a cell's pool (``ba-r4-75k``'s 2,048 RR sets, 2^20 pool
    elements): each compiles, its temporaries bounded by the pool and a
    few node-wide vectors."""
    from repro.core.coverage import _mesh_select_fns
    n = WIDTHS[width]
    mesh = Mesh(np.asarray(topo.devices[:1]), ("samples",))
    buf = NamedSharding(mesh, P("samples", None))
    vec = NamedSharding(mesh, P("samples"))
    rep = NamedSharding(mesh, P())
    fns = _mesh_select_fns(mesh)
    rows, elems = 1 << 11, 1 << 20
    flat = _sds((1, elems), jnp.int32, buf)
    valid = _sds((1, elems), jnp.bool_, buf)
    nrr = _sds((1,), jnp.int32, vec)
    cand = _sds((n,), jnp.bool_, rep)
    costs = _sds((n,), jnp.float32, rep)
    budget = _sds((), jnp.float32, rep)
    static = dict(num_rows=rows, n=n, k_steps=50, n_group=n, n_groups=1,
                  group_quota=50, use_costs=True)
    dummy = _sds((1,), jnp.float32, rep)
    for program, wvec, ew in (
            (fns.fused_variant, dummy, dummy),
            (fns.fused_variant_w, _sds((1,), jnp.float32, vec),
             _sds((1, elems), jnp.float32, buf))):
        c = program.lower(flat, flat, valid, nrr, wvec, ew, cand, costs,
                          budget, **static).compile()
        assert c.memory_analysis().temp_size_in_bytes < 16 * (elems + n)


def _edge_rows_sds(m, ec, sharding):
    """The shape of ``rrset.edge_row_table`` for m edges."""
    return _sds((-(-m // ec) + 2, 2, ec), jnp.int32, sharding)


def test_sampler_pack_has_no_gather_or_scatter(one_chip, chip_compiles):
    """The chunk left-pack at 512 lanes of 128-edge chunks: one fused
    compare-reduce, no gather, no scatter, the (B, EC, EC) compare never
    in memory; and no op of the sampler's ``rr.append`` scope gathers or
    scatters."""
    from repro.core import rrset
    n, m = WIDTHS["epinions"], 607_012     # the benchmark's BA r=4 graph
    b, ec = 512, rrset.EC_DEFAULT
    c = jax.jit(lambda a, v: rrset._pack_accepted(a, v, n, ec)).lower(
        _sds((b, ec), jnp.bool_, one_chip),
        _sds((b, ec), jnp.int32, one_chip)).compile()
    assert not re.search(r"\s(gather|scatter)\(", c.as_text())
    assert c.memory_analysis().temp_size_in_bytes < b * ec * 4

    hlo = rrset._queue_round.lower(
        jax.random.key(0), _sds((n + 1,), jnp.int32, one_chip),
        _edge_rows_sds(m, ec, one_chip), None, batch=b, qcap=n, n=n, m=m,
        dedup="none").compile().as_text()
    ops = [line for line in hlo.splitlines()
           if re.search(r"\s(gather|scatter)\(", line)]
    assert any("rr.edges" in line for line in ops)
    assert not any("rr.append" in line for line in ops)


# one sampling round at the benchmark's lanes and EC, and the pool append
# of its output, at pokec width: memory follows the queue rows held once
# and the elements drawn, not a copy per (B, n) array
ROUND_B, POKEC_M = 512, 32_655_950         # BA r=10 graph, both directions


@pytest.fixture(scope="module")
def pokec_round(one_chip, chip_compiles):
    """``_queue_round`` compiled at pokec width, the benchmark's lanes and
    EC (one compile for the tests below)."""
    from repro.core import rrset
    n, m, ec = WIDTHS["pokec"], POKEC_M, rrset.EC_DEFAULT
    return rrset._queue_round.lower(
        jax.random.key(0), _sds((n + 1,), jnp.int32, one_chip),
        _edge_rows_sds(m, ec, one_chip), None, batch=ROUND_B, qcap=n, n=n,
        m=m, dedup="none").compile()


def test_sampling_round_holds_its_queue_once(pokec_round):
    """The round returns its queue buffer, pad tail included, so its
    temporaries hold the (B, n + EC) queue about once (three copies when
    it returned a ``[:, :qcap]`` slice)."""
    from repro.core import rrset
    queue_bytes = ROUND_B * (WIDTHS["pokec"] + rrset.EC_DEFAULT) * 4
    assert pokec_round.memory_analysis().temp_size_in_bytes <= (
        1.1 * queue_bytes)


def test_edge_fetch_gathers_whole_rows(pokec_round):
    """The step's ``rr.edges`` scope reads the edges as rows of the edge-row
    table: every gather from an operand the size of the edge arrays (m) or
    of the table moves a slice of at least EC elements (no per-element
    gather from the edge arrays), and the scope runs no loop (a vmapped
    ``dynamic_slice`` of the window would be a loop over the lanes)."""
    from repro.core import rrset
    ec = rrset.EC_DEFAULT
    edge_sizes = {POKEC_M, (-(-POKEC_M // ec) + 2) * 2 * ec}
    hlo = pokec_round.as_text()
    size = {}
    for line in hlo.splitlines():
        d = re.match(r"\s*(?:ROOT\s+)?%(\S+)\s*=\s*\w+\[([\d,]*)\]", line)
        if d:
            size[d.group(1)] = int(np.prod(
                [int(x) for x in d.group(2).split(",") if x]))
    edges = [line for line in hlo.splitlines() if "rr.edges" in line]
    assert not [x for x in edges
                if re.search(r"\s(while|dynamic-slice)\(", x)]
    row_gathers = 0
    for line in edges:
        g = re.search(r"\sgather\(%(\S+?),.*slice_sizes=\{([\d,]+)\}",
                      line)
        if g and size[g.group(1)] in edge_sizes:
            moved = int(np.prod([int(x) for x in g.group(2).split(",")]))
            assert moved >= ec, line.strip()[:160]
            row_gathers += 1
    assert row_gathers >= 1


def test_edge_row_table_builds_without_a_loop(one_chip, chip_compiles):
    """The engines' edge-row table at pokec's m compiles to straight-line
    ops; laid out through a (2, R, EC) -> (R, 2, EC) transpose it compiled
    to a loop of dynamic slices, and took about a minute to compile."""
    from repro.core import rrset
    hlo = rrset.edge_row_table.lower(
        _sds((POKEC_M,), jnp.int32, one_chip),
        _sds((POKEC_M,), jnp.float32, one_chip),
        ec=rrset.EC_DEFAULT).compile().as_text()
    assert not re.search(r"\s(while|dynamic-slice)\(", hlo)


@pytest.mark.parametrize("weighted", [False, True])
def test_pool_append_follows_the_elements(weighted, topo, chip_compiles):
    """The row-offset append of a (512, n + EC) round at pokec width:
    temporaries of at most 64 MB whatever the width, and no op that makes
    an array of the batch's R·W slots (only the batch itself, as the
    program's parameter, a bitcast of it, or the loop carrying it)."""
    from repro.core import rrset
    from repro.core.coverage import _mesh_store_fns
    from repro.core.packing import PACK_WINDOW
    n = WIDTHS["pokec"]
    r, w = ROUND_B, n + rrset.EC_DEFAULT
    mesh = Mesh(np.asarray(topo.devices[:1]), ("samples",))
    buf = NamedSharding(mesh, P("samples", None))
    vec = NamedSharding(mesh, P("samples"))
    fns = _mesh_store_fns(mesh)
    cap = 1 << 22
    pool = (_sds((1, cap), jnp.int32, buf), _sds((1, cap), jnp.int32, buf),
            _sds((1, cap), jnp.bool_, buf))
    counters = (_sds((1,), jnp.int32, vec), _sds((1,), jnp.int32, vec))
    batch = (_sds((r, w), jnp.int32, buf), _sds((r,), jnp.int32, vec))
    if weighted:
        c = fns.append_rows_w.lower(
            *pool, _sds((1, cap), jnp.float32, buf), counters[0],
            counters[1], _sds((1,), jnp.float32, vec), *batch,
            _sds((r,), jnp.float32, vec), pack=PACK_WINDOW, n=n).compile()
    else:
        c = fns.append_rows.lower(*pool, *counters, *batch,
                                  pack=PACK_WINDOW, n=n).compile()
    assert c.memory_analysis().temp_size_in_bytes <= 64 << 20
    wide = []
    for line in c.as_text().splitlines():
        shape = re.match(r"\s*(?:ROOT\s+)?%\S+\s*=\s*\w+\[([\d,]*)\]\S*\s+"
                         r"([\w-]+)\(", line)
        if shape and shape.group(1):
            size = int(np.prod([int(x) for x in shape.group(1).split(",")]))
            if size == r * w and shape.group(2) not in (
                    "parameter", "bitcast", "get-tuple-element", "tuple"):
                wide.append(line.strip()[:120])
    assert not wide, wide
