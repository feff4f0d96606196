"""CI lint guard: tuning knobs must originate in ``core/config.py``.

PR 8 moved every magic-number tuning knob into the one typed
``FnsConfig`` tree; the historical module-level constants survive only as
*derived aliases* (``MAX_CLAUSES = _KCFG.max_clauses``) for import
compatibility. This guard fails the build if any registered knob name is
re-assigned a numeric (or numeric-dict) LITERAL at module level anywhere
outside ``core/config.py`` — i.e. if someone reintroduces a hard-coded
value instead of deriving it from the config tree.

Deliberately registry-based: env-derived constants
(``GRAPH_K = int(os.environ.get(...))``), protocol sentinels
(``FORMAT``, ``MAGIC``, ``DEAD_DISJUNCT``) and test fixtures are not
knobs, and a blanket "no module-level numbers" rule would drown the
signal. Add a name here when a knob constant is born, remove it when the
alias is deleted.

Run:  python tools/knob_guard.py   (exit 1 + report on violation)
"""
from __future__ import annotations

import ast
import os
import sys

# every name that was a scattered hard-coded knob before core/config.py;
# each may only appear outside core/config.py as a value DERIVED from a
# config instance (attribute access), never as a literal again
KNOB_REGISTRY = frozenset({
    "MAX_CLAUSES",                            # kernels/ops.py
    "V_CAP", "MEMBER_CAP", "AUTO_V_CAP_MAX",  # core/device_atlas.py
    "MAX_DISJUNCTS", "DEFAULT_DOMAIN",        # core/predicate.py
    "MIN_BUCKET", "GRAPH_BUILD_DEFAULTS",     # serve/retrieval.py
})

SCAN_ROOTS = ("src", "benchmarks", "tune", "tools")
CONFIG_MODULE = os.path.join("src", "repro", "core", "config.py")


def _is_literal_knob_value(node: ast.AST) -> bool:
    """A numeric literal, or a dict/tuple/list whose values are numeric
    literals (the shapes a re-hard-coded knob takes)."""
    if isinstance(node, ast.Constant):
        return isinstance(node.value, (int, float)) and not isinstance(
            node.value, bool)
    if isinstance(node, ast.UnaryOp) and isinstance(node.operand,
                                                    ast.Constant):
        return _is_literal_knob_value(node.operand)
    if isinstance(node, ast.Dict):
        return any(_is_literal_knob_value(v) for v in node.values)
    if isinstance(node, (ast.Tuple, ast.List)):
        return any(_is_literal_knob_value(e) for e in node.elts)
    return False


def check_file(path: str) -> list[str]:
    with open(path) as f:
        try:
            tree = ast.parse(f.read(), filename=path)
        except SyntaxError as e:
            return [f"{path}: unparseable ({e})"]
    bad = []
    for node in tree.body:  # module level only: knob constants live there
        targets = []
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        else:
            continue
        for t in targets:
            if (isinstance(t, ast.Name) and t.id in KNOB_REGISTRY
                    and _is_literal_knob_value(value)):
                bad.append(
                    f"{path}:{node.lineno}: knob {t.id!r} assigned a "
                    f"literal — derive it from core/config.py instead")
    return bad


def main(repo_root: str = ".") -> int:
    config_abs = os.path.abspath(os.path.join(repo_root, CONFIG_MODULE))
    violations: list[str] = []
    scanned = 0
    for root in SCAN_ROOTS:
        base = os.path.join(repo_root, root)
        if not os.path.isdir(base):
            continue
        for dirpath, _dirs, files in os.walk(base):
            for name in files:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(dirpath, name)
                if os.path.abspath(path) == config_abs:
                    continue
                scanned += 1
                violations.extend(check_file(path))
    if violations:
        print("knob guard FAILED:")
        for v in violations:
            print("  " + v)
        return 1
    print(f"knob guard OK ({scanned} files scanned, "
          f"{len(KNOB_REGISTRY)} registered knobs)")
    return 0


if __name__ == "__main__":
    sys.exit(main(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
                  or "."))
