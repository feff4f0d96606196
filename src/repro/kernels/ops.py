"""Jit'd public wrappers for the Pallas kernels, and the one platform choice.

The search path runs Pallas kernels on a TPU and their jnp oracles
(``kernels/ref.py``) on the CPU; ``on_tpu`` is the single place that
decides, and it refuses any other platform rather than silently running a
CPU formulation there. On the CPU these wrappers execute the kernel body
under the Pallas interpreter (bit-exact semantics, no Mosaic) — the form
the tests check against the oracles. ``predicate_tables`` converts a core
FilterPredicate into the dense clause tables ``filter_eval`` consumes.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core.config import AtlasConfig, KernelConfig
from repro.kernels.filter_eval import filter_eval as _filter_eval
from repro.kernels.filter_eval import filter_eval_batch as _filter_eval_batch

# legacy module-level name, derived from the one config origin
# (core/config.py); kept as an importable alias for existing callers
_KCFG = KernelConfig()
MAX_CLAUSES = _KCFG.max_clauses


def on_tpu() -> bool:
    """True on a TPU backend (Pallas kernels lower to Mosaic), False on
    the CPU (interpret mode / jnp oracles). Any other platform is an
    error: no CPU formulation may stand in for a device it was not
    written for."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            f"unsupported JAX platform {platform!r}: the search path runs "
            f"on 'tpu' (Pallas kernels) or 'cpu' (tests, interpret mode)")
    return platform == "tpu"


def filter_eval(metadata, fields, allowed, *, tn: int = _KCFG.filter_tile):
    return _filter_eval(metadata, fields, allowed, tn=tn,
                        interpret=not on_tpu())


def filter_eval_batch(metadata, fields, allowed, n_disj=None, bounds=None, *,
                      tn: int = _KCFG.filter_tile,
                      tag_cols: tuple[int, ...] = ()):
    return _filter_eval_batch(metadata, fields, allowed, n_disj, bounds,
                              tn=tn, interpret=not on_tpu(),
                              tag_cols=tag_cols)


def predicate_tables(pred, n_fields: int,
                     max_clauses: int = MAX_CLAUSES,
                     v_cap: int = AtlasConfig().v_cap_min
                     ) -> tuple[np.ndarray, np.ndarray]:
    """FilterPredicate -> (fields (C,) i32, allowed (C, v_cap) u8)."""
    fields = np.full(max_clauses, -1, np.int32)
    allowed = np.zeros((max_clauses, v_cap), np.uint8)
    for i, (f, vals) in enumerate(pred.clauses[:max_clauses]):
        fields[i] = f
        for v in vals:
            if 0 <= v < v_cap:
                allowed[i, v] = 1
    return fields, allowed
