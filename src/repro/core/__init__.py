from repro.core.imm import imm, imm_result, IMMSolver
from repro.core.problem import IMProblem, IMResult
from repro.core.engine import (SamplerEngine, RRBatch, register_engine,
                               get_engine, make_engine, list_engines,
                               resolve_engine_name, build_alias_table,
                               draw_roots)
from repro.core.coverage import (RRStore, IncrementalRRStore, DeviceRRStore,
                                 ShardedDeviceRRStore, SelectionSpec,
                                 build_store, merge_stores, occur_histogram,
                                 select_seeds, select_seeds_device,
                                 select_variant)
from repro.core.rrset import sample_rrsets_queue, to_lists
from repro.core.dense import (sample_rrsets_dense, membership_to_lists,
                              membership_to_padded)
from repro.core.lt import sample_rrsets_lt
from repro.core.forward import ic_spread, lt_spread
from repro.core.mrim import solve_mrim

__all__ = [
    "imm", "imm_result", "IMMSolver", "IMProblem", "IMResult",
    "SamplerEngine", "RRBatch", "register_engine", "get_engine",
    "make_engine", "list_engines", "resolve_engine_name",
    "build_alias_table", "draw_roots",
    "RRStore", "IncrementalRRStore", "DeviceRRStore",
    "ShardedDeviceRRStore", "SelectionSpec", "build_store",
    "merge_stores", "occur_histogram", "select_seeds", "select_seeds_device",
    "select_variant",
    "sample_rrsets_queue", "to_lists",
    "sample_rrsets_dense", "membership_to_lists", "membership_to_padded",
    "sample_rrsets_lt", "ic_spread", "lt_spread", "solve_mrim",
]
