"""The corpus of a configuration, made on the device from the seed.

One jitted call per configuration makes every row: unit vectors from a
Zipf mixture of anisotropic Gaussians on the sphere with a lognormal radial
factor, and integer metadata correlated with each row's mixture component.
The within-component spread and the query noise are scaled by sqrt(64/d),
so that the corpus keeps its cluster structure at any width: unscaled, a
2048-wide corpus is close to uniform on the sphere and no graph walk can
navigate it.

Metadata (``metadata.kind`` ``categorical``): one column per entry of
``vocab_sizes``, field f over that many values, with Zipf value
frequencies, the row's component's own value with probability ``corr``,
and ``empty_frac`` of entries unpopulated (-1). The schema is the
configuration's, the same for every seed.

The same seed gives the same rows on every run and device kind.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def rng_key(seed: int) -> jax.Array:
    """A JAX key from any non-negative seed, also one beyond 32 bits (JAX
    keeps only the low 32 bits of a plain seed while 64-bit mode is off)."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def spread_scale(d: int) -> float:
    """The width correction of the spread and the query noise."""
    return math.sqrt(64.0 / d)


@dataclasses.dataclass
class Corpus:
    """The ``n`` rows the index is built on."""

    vectors: jax.Array        # (n, d) float32, unit rows, on device
    metadata: np.ndarray      # (n, F) int32, on the host
    field_names: list[str]
    vocab_sizes: list[int]
    n: int

    @property
    def d(self) -> int:
        return int(self.vectors.shape[1])


def _zipf_logits(v: int, a: float, width: int) -> jnp.ndarray:
    """Log-probabilities of a Zipf(a) law over v values, -inf past v."""
    r = jnp.arange(1, width + 1, dtype=jnp.float32)
    return jnp.where(r <= v, -a * jnp.log(r), -jnp.inf)


@functools.partial(jax.jit, static_argnames=("rows", "d", "comps", "zipf",
                                             "noise", "radial"))
def _mixture(key, *, rows, d, comps, zipf, noise, radial):
    """Unit vectors of a Zipf mixture of anisotropic Gaussians, and each
    row's component."""
    kc, kp, ks, kr, ke = jax.random.split(key, 5)
    centers = jax.random.normal(kc, (comps, d), jnp.float32)
    centers = centers / jnp.linalg.norm(centers, axis=1, keepdims=True)
    comp = jax.random.categorical(kp, _zipf_logits(comps, zipf, comps),
                                  shape=(rows,))
    scales = (0.5 + jax.random.uniform(ks, (comps,))) * noise
    rad = jnp.exp(-0.5 * radial ** 2
                  + radial * jax.random.normal(kr, (rows,)))
    x = centers[comp] + jax.random.normal(ke, (rows, d)) * (
        scales[comp] * rad)[:, None]
    return x / jnp.linalg.norm(x, axis=1, keepdims=True), comp


@functools.partial(jax.jit, static_argnames=("width", "corr", "zipf",
                                             "empty", "comps"))
def _categorical(key, comp, v, *, width, corr, zipf, empty, comps):
    """(rows, fields) codes, field f over ``v[f]`` values (at most
    ``width``): the component's own value with probability ``corr``, else
    a Zipf draw; ``empty`` of entries -1."""
    rows, n_fields = comp.shape[0], v.shape[0]
    km, kz, ku, ke = jax.random.split(key, 4)
    own = jax.random.randint(km, (comps, n_fields), 0, v)[comp]
    r = jnp.arange(1, width + 1, dtype=jnp.float32)
    logits = jnp.where(r[None, :] <= v[:, None], -zipf * jnp.log(r), -jnp.inf)
    rand = jax.random.categorical(kz, logits, shape=(rows, n_fields))
    col = jnp.where(jax.random.uniform(ku, (rows, n_fields)) < corr, own,
                    rand)
    return jnp.where(jax.random.uniform(ke, (rows, n_fields)) < empty, -1,
                     col).astype(jnp.int32)


def make_corpus(cfg: dict, seed: int) -> Corpus:
    """The configuration's corpus of ``cfg["n"]`` rows from ``seed``.
    Vectors stay on the device; metadata comes to the host, where
    predicates and the reference read it."""
    d, n = int(cfg["d"]), int(cfg["n"])
    mix, meta = cfg["mixture"], cfg["metadata"]
    if meta["kind"] != "categorical":
        raise ValueError(f"unknown metadata kind {meta['kind']!r}")
    key = rng_key(seed)
    vectors, comp = _mixture(
        jax.random.fold_in(key, 1), rows=n, d=d,
        comps=int(mix["components"]), zipf=float(mix["component_zipf"]),
        noise=float(mix["noise"]) * spread_scale(d),
        radial=float(mix["radial_lognorm"]))
    vocab = [int(v) for v in meta["vocab_sizes"]]
    codes = _categorical(jax.random.fold_in(key, 2), comp,
                         jnp.asarray(vocab, jnp.int32), width=max(vocab),
                         corr=float(meta["corr"]),
                         zipf=float(meta["value_zipf"]),
                         empty=float(meta["empty_frac"]),
                         comps=int(mix["components"]))
    names = [f"field_{f}" for f in range(len(vocab))]
    return Corpus(vectors, np.asarray(codes), names, vocab, n)


@jax.jit
def _near(vectors, src, key, noise):
    x = vectors[src]
    x = x + noise * jax.random.normal(key, x.shape, x.dtype)
    return x / jnp.linalg.norm(x, axis=1, keepdims=True)


def queries_near(corpus: Corpus, src: np.ndarray, seed: int,
                 noise: float) -> np.ndarray:
    """Unit query vectors near rows ``src`` of the corpus: the row plus
    ``noise`` x sqrt(64/d) Gaussian noise, on the device."""
    key = jax.random.fold_in(rng_key(seed), 11)
    out = _near(corpus.vectors, jnp.asarray(src, jnp.int32), key,
                jnp.float32(noise * spread_scale(corpus.d)))
    return np.asarray(out)
