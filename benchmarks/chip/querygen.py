"""The one traffic generator: a configuration file plus a traffic file
give a seeded stream of query dicts in the program's wire format.

A traffic file (``traffic/<name>.json``) holds only parameters:

  query     ``layer`` (one mapping search per query), ``network`` (one
            schedule search over all the configuration's layers) or
            ``codse`` (a joint mapping x hardware sweep of the first
            layer over the configuration's whole grid);
  search    search settings added to the configuration's own;
  warmup    the warm-up queries, which reach the executables the
            window uses at a small size (see :func:`warmup`).

Each query takes a hardware point drawn from the configuration's grid and
a search seed, both from the run's seed, so no two queries in a window
are alike and no cache can answer one. Layer queries take the layers in
rounds, each round in a seeded order, so every seed sends the same mix.
"""
from __future__ import annotations

import itertools
from typing import Any, Iterator

import numpy as np

SEED_SPACE = 2**31 - 1


def axes(grid: dict[str, list]) -> tuple[list[int], list[float]]:
    pes = list(range(grid["num_pes"][0], grid["num_pes"][1] + 1,
                     grid["num_pes"][2]))
    bws = [float(b) for b in range(grid["noc_bw"][0], grid["noc_bw"][1] + 1,
                                   grid["noc_bw"][2])]
    return pes, bws


def _query(config: dict[str, Any], kind: str, *, layer: int,
           num_pes: int, noc_bw: float, seed: int, grid: dict[str, list],
           search: dict[str, Any]) -> dict[str, Any]:
    hw = {"num_pes": int(num_pes), "noc_bw": float(noc_bw),
          **config.get("hardware", {})}
    srch = {**config.get("search", {}), **search, "seed": int(seed)}
    if kind == "network":
        wl = {"layers": [dict(d) for d in config["layers"]]}
    else:
        wl = {"op": dict(config["layers"][layer])}
    if kind == "codse":
        pes, bws = axes(grid)
        hw.update(pe_range=pes, bw_range=bws,
                  area_budget_mm2=config["budgets"]["area_mm2"],
                  power_budget_mw=config["budgets"]["power_mw"])
    return {"workload": wl, "hardware": hw, "search": srch,
            "tag": f"{kind}:{layer}:{num_pes}:{noc_bw:g}:{seed}"}


def layer_order(config: dict[str, Any], traffic: dict[str, Any],
                rng: np.random.Generator) -> Iterator[int]:
    if traffic["query"] != "layer":
        return itertools.repeat(0)
    n = len(config["layers"])
    return itertools.chain.from_iterable(
        rng.permutation(n).tolist() for _ in itertools.count())


def window(config: dict[str, Any], traffic: dict[str, Any], seed: int
           ) -> Iterator[dict[str, Any]]:
    """The window's queries, endless and all distinct, from ``seed``."""
    rng = np.random.default_rng(seed)
    pes, bws = axes(config["grid"])
    used: set[int] = {0}
    for layer in layer_order(config, traffic, rng):
        s = 0
        while s in used:
            s = int(rng.integers(1, SEED_SPACE))
        used.add(s)
        yield _query(config, traffic["query"], layer=layer,
                     num_pes=pes[int(rng.integers(len(pes)))],
                     noc_bw=bws[int(rng.integers(len(bws)))], seed=s,
                     grid=config["grid"],
                     search=traffic.get("search", {}))


def warmup(config: dict[str, Any], traffic: dict[str, Any]
           ) -> list[dict[str, Any]]:
    """The warm-up queries, all with search seed 0, which the window
    never uses. Each entry of the traffic's ``warmup`` list gives a query
    kind, its search settings and optionally a smaller grid; a ``layer``
    entry makes one query per layer of the configuration."""
    out = []
    for w in traffic["warmup"]:
        grid = w.get("grid", config["grid"])
        pes, bws = axes(grid)
        layers = range(len(config["layers"])) if w["query"] == "layer" \
            else [0]
        out += [_query(config, w["query"], layer=i, num_pes=pes[-1],
                       noc_bw=bws[-1], seed=0, grid=grid,
                       search=w.get("search", {}))
                for i in layers]
    return out
