"""Device scopes of the compiled search programs, and the dispatch that
records them.

The search program names three scopes (``jax.named_scope``):
``filter_eval`` (the predicate sweep), ``anchor_select`` (a round's anchor
selection) and ``walk_hop`` (one lockstep walk iteration). They live in
the HLO ``op_name`` metadata only. A TPU profiler trace names each device
op by its HLO instruction (``%fusion.356 = ...``) without that metadata,
so the scope of a traced op is looked up here: the first call that
compiles a new executable of a search program records the op-name ->
scope map of its compiled HLO text in ``OP_SCOPES``, keyed by the HLO
module's name. The lowering and the compile hit JAX's caches right after
that call, so the record costs a text parse once per compiled shape.
"""
from __future__ import annotations

import re

from jax.profiler import TraceAnnotation

SCOPES = ("filter_eval", "anchor_select", "walk_hop")
OTHER = "other"

# HLO module name -> instruction name -> its innermost scope, ``other``, or
# None where two recorded executables of that name disagree
OP_SCOPES: dict[str, dict[str, str | None]] = {}

_MODULE = re.compile(r"HloModule ([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_OP = re.compile(r"^\s*(?:ROOT )?%(\S+) = ")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([^\s,]+)")


def scope_of_path(path: str) -> str:
    """The innermost known scope in an ``op_name`` path, else ``other``."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return OTHER


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Each instruction's scope in compiled HLO text: from its ``op_name``
    metadata, else, for a fusion XLA left without metadata, the one scope
    of the instructions in the computation it calls. Instructions with
    neither are left out."""
    ops: dict[str, tuple[str | None, str | None]] = {}
    in_comp: dict[str, set[str]] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = in_comp.setdefault(m.group(1), set())
            continue
        m = _OP.match(line)
        if m is None:
            continue
        path, callee = _OP_NAME.search(line), _CALLS.search(line)
        scope = scope_of_path(path.group(1)) if path else None
        ops[m.group(1)] = (scope, callee.group(1) if callee else None)
        if scope is not None and comp is not None:
            comp.add(scope)
    out = {}
    for name, (scope, callee) in ops.items():
        if scope is None and len(in_comp.get(callee, ())) == 1:
            (scope,) = in_comp[callee]
        if scope is not None:
            out[name] = scope
    return out


def record(hlo_text: str) -> None:
    """Add one compiled executable's op-name -> scope map to
    ``OP_SCOPES``."""
    m = _MODULE.search(hlo_text)
    if m is None:
        return
    ops = OP_SCOPES.setdefault(m.group(1), {})
    for name, scope in op_scopes(hlo_text).items():
        if ops.setdefault(name, scope) != scope:
            ops[name] = None


def dispatch_program(program, batch: int, *args, **kwargs):
    """Call a jitted search program under the host span ``fns.dispatch``
    and, when the call compiled a new executable (the program's cache
    grew), record that executable's scopes."""
    size = getattr(program, "_cache_size", None)
    before = size() if size is not None else 0
    with TraceAnnotation("fns.dispatch", batch=batch):
        out = program(*args, **kwargs)
    if size is not None and size() > before:
        record(program.lower(*args, **kwargs).compile().as_text())
    return out
