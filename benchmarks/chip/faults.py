"""Faults planted in the program under test, to show that ``check.py``
sees them: its tests plant each at a size the CPU holds, and
``control.py --fault <name>`` reads them at a cell's own size.

  drop-half   the co-DSE joint sweep evaluates every other design of each
              chunk and leaves the rest out, while still reporting the
              rows it kept by their own indices (half of the batch left
              out, the winners taken over the rest).

Each fault is a function of the program's ``repro`` package that plants
the fault and returns a function that takes it out again.
"""
from __future__ import annotations

from typing import Callable


def drop_half() -> Callable[[], None]:
    from repro.mapspace import codse
    evaluate = codse.evaluate_genes

    def every_other(op, space, genes, *, num_pes, noc_bw, **kw):
        res = evaluate(op, space, genes[::2], num_pes=num_pes[::2],
                       noc_bw=noc_bw[::2], **kw)
        for t in res.top + res.pareto:
            t["row"] *= 2
        return res

    codse.evaluate_genes = every_other

    def undo() -> None:
        codse.evaluate_genes = evaluate
    return undo


FAULTS = {"drop-half": drop_half}
