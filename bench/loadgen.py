"""What every traffic generator shares.

A traffic file (``bench/traffic/<mix>.json``) names its ``generator``, a
module ``bench/traffic/<generator>.py`` that the harness loads by that
name. The module defines ``Loop(cfg, traffic, seed, seconds, workdir)``,
which builds the corpus and the index (``setup``), warms every program
shape the window will use (``warm``), drives the timed window
(``window(annotate)``), finishes what the window left in flight
(``finish``), frees the program's state (``close``), and hands the harness
the window's record (``describe``, ``counters``), what the reference
compares (``comparison``) and the end-to-end metrics it reports
(``end_to_end(judged)``); ``k`` and ``phases`` (set-up seconds by phase)
are attributes. A generator that draws predicates takes their ``shape``
from the traffic file, a module ``bench/shapes/<shape>.py`` with
``draw(corpus, target, rng)`` (see ``shape``). The harness (``run.py``)
owns the clock, the trace and the report.
"""
from __future__ import annotations

import contextlib
from pathlib import Path

import jax
import numpy as np

import plugin

BENCH = Path(__file__).resolve().parent


def annotate(name: str, on: bool):
    """A host span the trace reduction attributes idle device time to."""
    return jax.profiler.TraceAnnotation(name) if on \
        else contextlib.nullcontext()


def program_config(cfg: dict, traffic: dict):
    """The program's one configuration object: the deployment's index
    settings and the traffic's serving settings."""
    from repro.core.config import FnsConfig

    knobs = dict(cfg["index"])
    knobs.update(traffic.get("serve", {}))
    return FnsConfig().with_knobs(knobs)


def dataset(corpus):
    """The corpus as the program's ``Dataset`` (vectors on the host)."""
    from repro.core.types import Dataset

    n = corpus.n
    return Dataset(np.asarray(corpus.vectors[:n]), corpus.metadata[:n],
                   corpus.field_names, corpus.vocab_sizes)


def shape(name: str, bench: Path = BENCH):
    """The predicate shape ``bench/shapes/<name>.py``: its
    ``draw(corpus, target, rng)`` returns a plain description
    (``predicates.py``) whose selectivity on the corpus is near
    ``target``."""
    return plugin.load(bench / "shapes" / f"{name}.py")
