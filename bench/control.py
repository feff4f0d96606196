#!/usr/bin/env python3
"""Read the numbers ``correct`` compares for the program and for its
control, on several seeds, in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed it runs the cell as ``run.py`` does (set-up, warm-up, a
window of ``--seconds``) and then compares two sets of answers to the
same queries with the reference: the program's, and the control's. The
control is the reference put in the program's place one precision below
the configuration's: the exact filtered top-k ranked by cosine scores of
bfloat16 operands where the configuration states float32. A limit is
sound when every seed of the program reads below it and the control reads
above it. One JSON line per seed:
``{"seed", "program": {...}, "control": {...}}``.

The benchmark's own runs never run this. It needs a TPU, as ``run.py``
does.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from pathlib import Path

import run


def readings(workload: str, seed: int, seconds: float, *,
             root: Path = run.ROOT) -> dict:
    """The program's and the control's numbers for one seed."""
    import reference

    loop = run.make_loop(run.load_cell(workload, root), seed, seconds, root)
    loop.setup()
    loop.warm()
    loop.window(annotate=False)
    loop.finish()
    loop.close()
    gc.collect()
    cmp = loop.comparison()
    args = (cmp["vectors"], cmp["queries"])
    program = reference.judge(*args, cmp["answers"], cmp["allowed"], loop.k)
    program.update(cmp["checks"])
    control_answers = reference.exact_answers(*args, cmp["allowed"], loop.k,
                                              low_precision=True)
    control = reference.judge(*args, control_answers, cmp["allowed"], loop.k)
    return {"seed": seed, "queries": len(cmp["answers"]),
            "program": program, "control": control}


def main(argv=None, *, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    try:
        run.device_info(int(spec["cell"]["chips"]), require_tpu)
    except run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    for seed in args.seeds:
        print(json.dumps(readings(args.workload, seed, args.seconds)),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
