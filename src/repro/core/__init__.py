"""Kairos core: temporal graph model, TGER time-first index, selective
indexing and the cold store.  The TemporalEdgeMap primitives sit above the
engine, in ``repro.core.edgemap``."""
from repro.core.temporal_graph import TemporalGraph, from_edges  # noqa: F401
from repro.core.predicates import OrderingPredicateType  # noqa: F401
from repro.core.tger import TGERIndex, build_tger  # noqa: F401
from repro.core.selective import CostModel, decide_access  # noqa: F401
from repro.core.coldstore import ColdChunk, ColdStore  # noqa: F401
