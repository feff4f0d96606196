"""Tag-set fields: metadata fields whose row value is a set of labels
(DESIGN.md §8.1).

A tag-set field over ``V`` labels stores each row's labels as a V-bit mask
in ``ceil(V/32)`` int32 words held in adjacent metadata columns: label
``l`` is bit ``l & 31`` of the word in column ``first + (l >> 5)``. An
empty set is all-zero words. The schema is a tuple of ``(first column,
V)`` pairs (``TagFields``), declared on the ``Dataset`` and carried by
every index built from it; a column of a tag-set field is never read as a
categorical code.

A tag clause (``predicate.HasAny``) passes a row that carries any of its
labels: ``(row words & clause words) != 0`` over the field's words. Its
clause-table row is the same value bitmap a value-set clause has, so the
clause tables keep their layout and the atlas presence bitmap of the field
is the OR of its rows' masks. The bitmaps are ``v_cap`` bits wide, so a
tag vocabulary is served up to the value-bitmap ceiling
(``AtlasConfig.auto_v_cap_max``).
"""
from __future__ import annotations

from typing import Iterable, Sequence

import numpy as np

from repro.core.config import AtlasConfig

TagFields = tuple  # tuple[(first column, n_labels), ...]

MAX_LABELS = AtlasConfig().auto_v_cap_max


def n_tag_words(n_labels: int) -> int:
    """int32 columns holding a V-label mask."""
    return -(-int(n_labels) // 32)


def normalize_tag_fields(tag_fields) -> TagFields:
    """``{column: V}`` or ``[(column, V), ...]`` -> the sorted canonical
    tuple of int pairs (hashable: it keys compiled programs)."""
    items = tag_fields.items() if hasattr(tag_fields, "items") \
        else (tag_fields or ())
    return tuple(sorted((int(c), int(v)) for c, v in items))


def check_tag_fields(tag_fields: TagFields, n_columns: int,
                     v_cap: int | None = None) -> None:
    """Each tag-set field fits the metadata and the value bitmaps, and no
    two share a column. Raises ValueError naming the field."""
    taken: set[int] = set()
    for col, v in tag_fields:
        if v > MAX_LABELS:
            raise ValueError(
                f"tag-set field at column {col} declares {v} labels; tag "
                f"sets are served for vocabularies up to "
                f"auto_v_cap_max={MAX_LABELS} (a wider vocabulary needs a "
                f"sparse per-row label layout)")
        words = range(col, col + n_tag_words(v))
        if v <= 0 or col < 0 or words.stop > n_columns:
            raise ValueError(
                f"tag-set field at column {col} over {v} labels needs "
                f"columns [{col}, {words.stop}) of {n_columns}")
        if v_cap is not None and v > v_cap:
            raise ValueError(
                f"tag-set field at column {col} declares {v} labels but "
                f"the value bitmaps hold {v_cap}; build with atlas.v_cap "
                f">= {v}")
        if taken.intersection(words):
            raise ValueError(f"tag-set field at column {col} overlaps "
                             f"another tag-set field")
        taken.update(words)


def first_columns(tag_fields: TagFields) -> tuple[int, ...]:
    """The first column of each tag-set field: what the kernel's static
    ``tag_cols`` takes."""
    return tuple(col for col, _ in tag_fields)


def tag_columns(tag_fields: TagFields) -> dict[int, tuple[int, int]]:
    """Every column a tag-set field occupies -> (first column, V)."""
    return {c: (col, v) for col, v in tag_fields
            for c in range(col, col + n_tag_words(v))}


def pack_labels(label_sets: Iterable[Iterable[int]],
                n_labels: int) -> np.ndarray:
    """Rows' label sets -> (rows, ceil(V/32)) int32 mask words."""
    sets = [np.asarray(list(s), np.int64) for s in label_sets]
    out = np.zeros((len(sets), n_tag_words(n_labels)), np.uint32)
    for i, s in enumerate(sets):
        if s.size and (s.min() < 0 or s.max() >= n_labels):
            raise ValueError(f"row {i} has a label outside [0, {n_labels})")
        np.bitwise_or.at(out[i], s >> 5,
                         np.left_shift(np.uint32(1), (s & 31)
                                       .astype(np.uint32)))
    return out.view(np.int32)


def label_bits(words: np.ndarray, n_labels: int) -> np.ndarray:
    """(rows, ceil(V/32)) int32 mask words -> (rows, V) bool."""
    w = np.ascontiguousarray(words).view(np.uint32)
    bits = (w[:, :, None] >> np.arange(32, dtype=np.uint32)) & 1
    return bits.reshape(w.shape[0], -1)[:, :n_labels].astype(bool)


def label_postings(metadata: np.ndarray, col: int,
                   n_labels: int) -> tuple[np.ndarray, np.ndarray]:
    """Every (row, label) pair of the tag-set field at ``col``: the row
    indices and labels, ordered by row then label."""
    words = np.asarray(metadata)[:, col:col + n_tag_words(n_labels)]
    return np.nonzero(label_bits(words, n_labels))


def stray_labels(metadata: np.ndarray, tag_fields: TagFields) -> list[int]:
    """First columns of the tag-set fields whose words set a bit at or
    beyond the field's V (labels the schema does not have)."""
    meta = np.asarray(metadata)
    bad = []
    for col, v in tag_fields:
        last = col + n_tag_words(v) - 1
        spare = 32 * n_tag_words(v) - v
        if spare and (meta[:, last].view(np.uint32)
                      >> np.uint32(32 - spare)).any():
            bad.append(col)
    return bad


def value_max(metadata: np.ndarray, tag_fields: TagFields = ()) -> int:
    """The largest code a value bitmap must hold: categorical columns'
    largest code, a tag-set field's largest label (V - 1). -1 for an
    empty corpus of categorical fields."""
    meta = np.asarray(metadata)
    tagged = tag_columns(tag_fields)
    plain = [c for c in range(meta.shape[1]) if c not in tagged]
    vmax = int(meta[:, plain].max()) if plain and meta.shape[0] else -1
    return max([vmax] + [v - 1 for _, v in tag_fields])


def field_domains(sizes: Sequence[int],
                  tag_fields: TagFields) -> tuple[int, ...]:
    """Per-column domains with a tag-set field's first column at V and its
    other word columns at 0 (no code is ever read there)."""
    out = [int(s) for s in sizes]
    for col, v in tag_fields:
        out[col] = v
        for c in range(col + 1, col + n_tag_words(v)):
            out[c] = 0
    return tuple(out)
