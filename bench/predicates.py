"""Plain predicate descriptions, and the program's predicate built from one.

A description is the JSON-able form the traffic generator keeps for every
query: ``{"any": [conjunction, ...]}``, a disjunction of conjunctions,
each a list of clauses ``{"f": field, "in": [codes]}`` or
``{"f": field, "lo": a, "hi": b}`` (inclusive). The reference evaluates a
description over the metadata with numpy alone (``mask``); the program
gets a ``FilterPredicate`` built from the same description
(``to_program``), so the two sides share no evaluation code.
"""
from __future__ import annotations

import numpy as np


def mask(desc: dict, metadata: np.ndarray) -> np.ndarray:
    """Rows of ``metadata`` (rows, F) that pass ``desc``."""
    out = np.zeros(metadata.shape[0], bool)
    for conj in desc["any"]:
        m = np.ones(metadata.shape[0], bool)
        for c in conj:
            col = metadata[:, c["f"]]
            if "in" in c:
                m &= np.isin(col, np.asarray(c["in"], np.int64))
            else:
                m &= (col >= c["lo"]) & (col <= c["hi"])
        out |= m
    return out


def to_program(desc: dict):
    """The program's conjunctive ``FilterPredicate`` for a description
    that is one conjunction of value sets, the only form a shape in
    ``bench/shapes/`` draws today."""
    from repro.core.types import FilterPredicate

    conjs = desc["any"]
    if len(conjs) != 1 or not all("in" in c for c in conjs[0]):
        raise ValueError(f"no program predicate for {desc!r}: only one "
                         f"conjunction of value sets")
    return FilterPredicate.make({c["f"]: c["in"] for c in conjs[0]})
