"""The corpus of a ``labels_and_date`` configuration, made on the device
from the seed: a paper store whose rows carry a set of subject labels and
an update date.

Vectors are ``datagen``'s Zipf mixture of anisotropic Gaussians (the same
law, width correction and key as a ``categorical`` corpus). Metadata
(``metadata`` of the configuration):

* ``labels``: a set of 1 to ``max_per_row`` labels over ``n_labels``,
  held as the program's tag-set layout: a ``n_labels``-bit mask in
  ceil(n_labels/32) int32 columns from column 0 on (bit ``l & 31`` of
  column ``l >> 5``). Label popularity is Zipf(``label_zipf``) over a
  random order of the labels, drawn per seed. The first label is the row's mixture component's own label with
  probability ``primary_corr``, else a Zipf draw; each further label is a
  Zipf draw, ``extra_mean`` of them on average (Poisson, capped), a
  repeat collapsing into one.
* ``update_date``: whole days over ``days``, the last column. The density
  grows by ``growth_per_year`` a year; with probability ``date_corr`` a
  row's date is its component's own date plus a Gaussian of
  ``date_spread_days``, else a draw from the growth law.

The same seed gives the same rows on every run and device kind.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import datagen

WORD = 32


def label_words(n_labels: int) -> int:
    return -(-n_labels // WORD)


@functools.partial(jax.jit, static_argnames=("n_labels", "max_per_row",
                                             "comps", "zipf", "corr",
                                             "extra_mean"))
def _labels(key, comp, *, n_labels, max_per_row, comps, zipf, corr,
            extra_mean):
    """(rows, ceil(n_labels/32)) int32 label masks."""
    rows = comp.shape[0]
    ko, kp, ku, kx, kn = jax.random.split(key, 5)
    # popularity rank -> label: a taxonomy's numbering is not its ranking
    rank = jax.random.permutation(kp, n_labels)
    logits = datagen._zipf_logits(n_labels, zipf, n_labels)
    own = rank[jax.random.categorical(ko, logits, shape=(comps,))][comp]
    draws = rank[jax.random.categorical(kx, logits,
                                        shape=(rows, max_per_row))]
    first = jnp.where(jax.random.uniform(ku, (rows,)) < corr, own,
                      draws[:, 0])
    labels = draws.at[:, 0].set(first)
    count = 1 + jnp.minimum(jax.random.poisson(kn, extra_mean, (rows,)),
                            max_per_row - 1)
    live = jnp.arange(max_per_row)[None, :] < count[:, None]
    words = jnp.arange(label_words(n_labels))
    bit = jnp.left_shift(jnp.uint32(1), (labels & 31).astype(jnp.uint32))
    hit = live[:, :, None] & ((labels >> 5)[:, :, None] == words)
    out = jnp.zeros((rows, words.size), jnp.uint32)
    for s in range(max_per_row):
        out = out | jnp.where(hit[:, s, :], bit[:, s, None], 0)
    return jax.lax.bitcast_convert_type(out, jnp.int32)


@functools.partial(jax.jit, static_argnames=("days", "growth", "comps",
                                             "corr", "spread"))
def _dates(key, comp, *, days, growth, comps, corr, spread):
    """(rows,) int32 day numbers in [0, days)."""
    rows = comp.shape[0]
    ko, ku, kc, kn = jax.random.split(key, 4)
    rate = math.log1p(growth) / 365.25          # per day

    def law(u):
        """Inverse CDF of a density growing as exp(rate * day)."""
        return jnp.log1p(u * math.expm1(rate * days)) / rate

    own = law(jax.random.uniform(ko, (comps,)))[comp]
    near = own + spread * jax.random.normal(kn, (rows,))
    day = jnp.where(jax.random.uniform(kc, (rows,)) < corr, near,
                    law(jax.random.uniform(ku, (rows,))))
    return jnp.clip(jnp.floor(day), 0, days - 1).astype(jnp.int32)


def make_corpus(cfg: dict, seed: int) -> datagen.Corpus:
    """The configuration's corpus of ``cfg["n"]`` rows from ``seed``: a
    ``datagen.Corpus`` whose ``tag_fields``, ((0, n_labels),), is the
    program's schema for its label columns. Vectors stay on the device;
    metadata comes to the host."""
    d, n = int(cfg["d"]), int(cfg["n"])
    mix, meta = cfg["mixture"], cfg["metadata"]
    if meta["kind"] != "labels_and_date":
        raise ValueError(f"not a labels_and_date configuration: "
                         f"{meta['kind']!r}")
    lab, date = meta["labels"], meta["update_date"]
    comps = int(mix["components"])
    key = datagen.rng_key(seed)
    vectors, comp = datagen._mixture(
        jax.random.fold_in(key, 1), rows=n, d=d, comps=comps,
        zipf=float(mix["component_zipf"]),
        noise=float(mix["noise"]) * datagen.spread_scale(d),
        radial=float(mix["radial_lognorm"]))
    v = int(lab["n_labels"])
    words = _labels(jax.random.fold_in(key, 2), comp, n_labels=v,
                    max_per_row=int(lab["max_per_row"]), comps=comps,
                    zipf=float(lab["label_zipf"]),
                    corr=float(lab["primary_corr"]),
                    extra_mean=float(lab["extra_mean"]))
    days = int(date["days"])
    day = _dates(jax.random.fold_in(key, 3), comp, days=days,
                 growth=float(date["growth_per_year"]), comps=comps,
                 corr=float(date["date_corr"]),
                 spread=float(date["date_spread_days"]))
    metadata = np.concatenate([np.asarray(words), np.asarray(day)[:, None]],
                              axis=1)
    w = label_words(v)
    names = (["labels"] + [f"labels.{i}" for i in range(1, w)]
             + ["update_date"])
    vocab = [v] + [0] * (w - 1) + [days]
    corpus = datagen.Corpus(vectors, metadata, names, vocab, n)
    corpus.tag_fields = ((0, v),)
    return corpus


def has_any(metadata: np.ndarray, field: int, labels) -> np.ndarray:
    """Rows whose label words from column ``field`` on hold any of
    ``labels``: the reference's own bit test, in numpy."""
    out = np.zeros(metadata.shape[0], bool)
    for lab in labels:
        word = metadata[:, field + lab // WORD].astype(np.int64)
        out |= ((word >> (lab % WORD)) & 1).astype(bool)
    return out


def label_matrix(corpus) -> np.ndarray:
    """(n, n_labels) bool: which labels each row carries."""
    meta = corpus.metadata[:corpus.n]
    return np.stack([has_any(meta, 0, [lab])
                     for lab in range(corpus.tag_fields[0][1])], axis=1)

