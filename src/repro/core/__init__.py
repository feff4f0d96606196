from repro.core.atlas import AnchorAtlas
from repro.core.device_atlas import DeviceAtlas, pack_dnf, pack_predicates
from repro.core.graph import Graph, build_alpha_knn, graph_stats
from repro.core.hnsw import HNSW
from repro.core.predicate import (DNF, And, FilterExpr, HasAny, In, Not,
                                  Or, Range, as_dnf, compile_to_dnf)
from repro.core.search import FiberIndex, SearchParams, run_queries, search
from repro.core.types import Dataset, FilterPredicate, Query, SearchStats, WalkStats

__all__ = ["AnchorAtlas", "DeviceAtlas", "pack_predicates", "pack_dnf",
           "Graph", "build_alpha_knn", "graph_stats", "HNSW", "FiberIndex",
           "SearchParams", "run_queries", "search", "Dataset",
           "FilterPredicate", "Query", "SearchStats", "WalkStats",
           "FilterExpr", "In", "Range", "HasAny", "And", "Or", "Not", "DNF",
           "compile_to_dnf", "as_dnf"]
