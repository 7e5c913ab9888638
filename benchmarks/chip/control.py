"""Readings that set the limits of ``check.py``: the program's and the
control's, over many seeds, at a cell's own size and load.

    python3 benchmarks/chip/control.py --workload <cell> \\
        --seeds 11 12 13 ... --seconds <s> [--fault <name>]

One process sets up once, then per seed runs a window of ``--seconds``
at the cell's load and reads each compared number twice: for what the
program answered, and for the control, the plain reference in the
program's place at bfloat16 (``check.control``) on the same queries.
With ``--fault``, the program answers with that fault of ``faults.py``
planted after the warm-up, so its readings are the fault's. It prints
one JSON line per seed and, last, the largest program reading and the
smallest reading of the control and of the program. The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import bench
import faults


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = bench.CACHE_DIR
    sys.path.insert(0, os.path.join(bench.ROOT, "src"))
    import jax
    import check
    import querygen
    bench.require_chips(jax, cell.chips)
    session = bench.make_session(cell.chips)
    for wire in querygen.warmup(cell.config, cell.traffic):
        bench.run_query(session, wire)
    if args.fault:
        faults.FAULTS[args.fault]()
    ref = check.Reference(cell.config)
    lower: dict[str, float] = {}
    upper: dict[str, float] = {}
    least: dict[str, float] = {}
    for seed in args.seeds:
        _, _, recs = bench.window(
            session, querygen.window(cell.config, cell.traffic, seed),
            args.seconds)
        run = bench.Run(cell, 0.0, 0.0, 0.0, recs)
        answers = [bench.answer(cell, q) for q in run.done]
        prog = check.readings(ref, answers)
        ctrl = check.readings(ref, check.control(ref, answers))
        print(json.dumps({"seed": seed, "answers": len(answers),
                          "failed": len(recs) - len(answers),
                          "program": prog, "control": ctrl}), flush=True)
        for k, v in prog.items():
            lower[k] = max(lower.get(k, 0.0), v)
            least[k] = min(least.get(k, float("inf")), v)
        for k, v in ctrl.items():
            upper[k] = min(upper.get(k, float("inf")), v)
    print(json.dumps({"lower": lower, "upper": upper,
                      "program_least": least}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
