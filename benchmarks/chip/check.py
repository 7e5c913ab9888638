"""The comparison that decides a run's ``correct``.

The harness turns each answer the timed queries returned into a plain
record (the winning dataflows as text in the paper's notation, the
hardware points and the statistics the program reported). This module
recomputes every reported statistic with the plain reference
(``reference/``, the faithful engine in exact arithmetic) and reads:

  stat_gap   the widest relative gap between a statistic the program
             reported and the reference's value for the same design;
  total_gap  (network) the widest relative gap between the schedule's
             reported totals and the sum of the reference's per-layer
             values plus the reported edge terms;
  topk_regret  (co-DSE) how far the reported best joint designs fall
             behind the best ones the sweep held: the reference rescans
             the whole hardware grid for every mapping the answer names
             (the search winner and each reported design's mapping, all
             of them rows of the sweep), and the j-th best EDP of the
             reported designs is held against the j-th best of that
             rescan. A sweep that left designs out, or merged its chunks'
             winners wrongly, reports a j-th design that the rescan beats.

``control`` puts the reference in the program's place at the nearest
precision below the configuration's float32: each statistic it reports
is the reference's value rounded to bfloat16, the most favourable result
any bfloat16 computation could give.
"""
from __future__ import annotations

import math
from typing import Any, Iterable

import ml_dtypes
import numpy as np

from reference.cluster_analysis import Backend
from reference.directives import parse
from reference.energy import AreaPowerModel
from reference.model import analyze
from reference.performance import HWConfig
from reference import tensor_analysis as ta

STATS = ("runtime", "energy_pj", "edp")
# The program judges the area and power budgets in float32; a design
# within this share of a budget may fall on either side of it there, so
# the rescan counts it only where the answer reports it.
BUDGET_MARGIN = 1e-5
COST_STATS = ("area_mm2", "power_mw")
BUILDERS = {"conv2d": ta.conv2d, "fc": ta.fc}
AREA_POWER = AreaPowerModel()


def layer_op(d: dict[str, Any]):
    """A reference LayerOp from a configuration's layer entry."""
    kw = {k: v for k, v in d.items() if k not in ("type", "name")}
    return BUILDERS[d["type"]](d["name"], **kw)


def dataflow(text: str):
    """Parse a dataflow printed as ``Dataflow <name> { ... }``."""
    body = text[text.index("{") + 1:text.rindex("}")]
    return parse(body)


def hwconfig(config: dict[str, Any], num_pes: int, noc_bw: float):
    return HWConfig(num_pes=int(num_pes), noc_bw=float(noc_bw),
                    **config.get("hardware", {}))


def array_backend() -> Backend:
    """The reference's exact Python arithmetic on scalars, and numpy's on
    arrays: with the NoC bandwidth an array, one analysis gives every
    bandwidth of a grid row, each as the scalar analysis would."""
    def arrays(*v) -> bool:
        return any(isinstance(x, np.ndarray) for x in v)

    return Backend(
        maximum=lambda a, b: np.maximum(a, b) if arrays(a, b)
        else (a if a >= b else b),
        minimum=lambda a, b: np.minimum(a, b) if arrays(a, b)
        else (a if a <= b else b),
        where=lambda c, t, f: np.where(c, t, f) if arrays(c)
        else (t if c else f),
        floordiv=lambda a, b: a // b,
    )


def body(df_text: str) -> str:
    """A dataflow's directives, without its name."""
    return df_text[df_text.index("{"):]


def rel_gap(got: float, want: float) -> float:
    if got == want:
        return 0.0
    if not (math.isfinite(got) and math.isfinite(want)):
        return math.inf
    return abs(got - want) / max(abs(want), 1e-30)


class Reference:
    """The reference's statistics of (layer, dataflow, hardware point),
    memoized, with the co-DSE sweep's area, power and leakage terms."""

    def __init__(self, config: dict[str, Any]):
        self.config = config
        self.ops = [layer_op(d) for d in config["layers"]]
        self._memo: dict[tuple, dict[str, float]] = {}

    def stats(self, layer: int, df_text: str, num_pes: int,
              noc_bw: float, sweep: bool = False) -> dict[str, float]:
        key = (layer, df_text, int(num_pes), float(noc_bw), sweep)
        if key not in self._memo:
            s = analyze(self.ops[layer], dataflow(df_text),
                        hwconfig(self.config, num_pes, noc_bw))
            out = {"runtime": float(s.runtime),
                   "energy_pj": float(s.energy_pj),
                   "l1_kb": float(s.l1_req_kb), "l2_kb": float(s.l2_req_kb)}
            if sweep:
                sram = out["l1_kb"] * num_pes + out["l2_kb"]
                out["area_mm2"] = AREA_POWER.area(num_pes, sram, noc_bw)
                out["power_mw"] = AREA_POWER.power(num_pes, sram, noc_bw)
                out["energy_pj"] += AREA_POWER.static_energy_pj(
                    out["area_mm2"], out["runtime"])
            out["edp"] = out["energy_pj"] * out["runtime"]
            self._memo[key] = out
        return self._memo[key]

    def grid(self, df_text: str) -> dict[str, np.ndarray]:
        """The sweep's EDP, area and power of the first layer's dataflow
        at every point of the configuration's grid, as (PEs, bandwidth)
        arrays: one analysis per PE count, the bandwidths as an array."""
        key = ("grid", body(df_text))
        if key not in self._memo:
            grid = self.config["grid"]
            pes = range(grid["num_pes"][0], grid["num_pes"][1] + 1,
                        grid["num_pes"][2])
            bws = np.arange(grid["noc_bw"][0], grid["noc_bw"][1] + 1,
                            grid["noc_bw"][2]).astype(np.float32)
            bw = bws.astype(np.float64)
            df, xp = dataflow(df_text), array_backend()
            rows: dict[str, list] = {"edp": [], "area_mm2": [],
                                     "power_mw": []}
            for p in pes:
                s = analyze(self.ops[0], df, HWConfig(
                    num_pes=p, noc_bw=bw, **self.config.get("hardware", {})),
                    xp)
                sram = float(s.l1_req_kb) * p + float(s.l2_req_kb)
                area = AREA_POWER.area(p, sram, bw)
                runtime = np.broadcast_to(np.asarray(s.runtime, np.float64),
                                          bw.shape)
                energy = s.energy_pj + AREA_POWER.static_energy_pj(
                    area, runtime)
                rows["edp"].append(energy * runtime)
                rows["area_mm2"].append(area)
                rows["power_mw"].append(AREA_POWER.power(p, sram, bw))
            self._memo[key] = {k: np.stack(v) for k, v in rows.items()}
            self._memo[key].update(
                pes=np.asarray(pes), bws=bws.astype(np.float64))
        return self._memo[key]

    def topk_regret(self, mapping: str, top: list[dict[str, Any]]
                    ) -> float:
        """The widest share by which the j-th best reported design's EDP
        lies above the j-th best of the rescan of every mapping named."""
        budgets = self.config["budgets"]
        named = {body(d["dataflow"]): d["dataflow"]
                 for d in [{"dataflow": mapping}, *top]}
        pool, reported = [], []
        for b, text in named.items():
            g = self.grid(text)
            keep = ((g["area_mm2"] <= budgets["area_mm2"]
                     * (1 - BUDGET_MARGIN))
                    & (g["power_mw"] <= budgets["power_mw"]
                       * (1 - BUDGET_MARGIN)))
            for d in top:
                if body(d["dataflow"]) == b:
                    i = int(np.searchsorted(g["pes"], d["num_pes"]))
                    j = int(np.searchsorted(g["bws"], d["noc_bw"]))
                    if i == len(g["pes"]) or j == len(g["bws"]) \
                            or g["pes"][i] != d["num_pes"] \
                            or g["bws"][j] != d["noc_bw"]:
                        return math.inf
                    keep[i, j] = True
                    reported.append(float(g["edp"][i, j]))
            pool.append(g["edp"][keep])
        best = np.sort(np.concatenate(pool))[:len(reported)]
        return max([0.0] + [(r - w) / w for r, w in
                            zip(sorted(reported), best.tolist())])


def _gaps(got: dict[str, float], want: dict[str, float],
          keys: Iterable[str]) -> float:
    return max(rel_gap(float(got[k]), want[k]) for k in keys)


def readings(ref: Reference, answers: list[dict[str, Any]],
             worst: dict[str, int] | None = None) -> dict[str, float]:
    """The widest reading of each compared number over ``answers``;
    ``worst``, if given, receives the index of the answer that gave it."""
    out: dict[str, float] = {}
    worst = {} if worst is None else worst

    def take(name: str, v: float) -> None:
        if v > out.get(name, -1.0):
            out[name], worst[name] = v, i

    for i, ans in enumerate(answers):
        kind = ans["kind"]
        hw = ans["hw"]
        if kind == "layer":
            b = ans["best"]
            take("stat_gap", _gaps(b, ref.stats(
                ans["layer"], b["dataflow"], hw["num_pes"], hw["noc_bw"]),
                STATS))
        elif kind == "codse":
            m = ans["mapping"]
            take("stat_gap", _gaps(m, ref.stats(
                0, m["dataflow"], hw["num_pes"], hw["noc_bw"]), STATS))
            for d in ans["top"]:
                want = ref.stats(0, d["dataflow"], d["num_pes"],
                                 d["noc_bw"], sweep=True)
                take("stat_gap", _gaps(d, want, STATS + COST_STATS))
            take("topk_regret", ref.topk_regret(m["dataflow"], ans["top"]))
        elif kind == "network":
            runtime = energy = 0.0
            for j, lay in enumerate(ans["layers"]):
                want = ref.stats(j, lay["dataflow"], hw["num_pes"],
                                 hw["noc_bw"])
                take("stat_gap", _gaps(lay, want, ("runtime", "energy_pj")))
                runtime += want["runtime"] + lay["edge_cycles"]
                energy += want["energy_pj"] + lay["edge_energy_pj"]
            take("total_gap", max(rel_gap(ans["runtime"], runtime),
                                  rel_gap(ans["energy_pj"], energy)))
        else:
            raise ValueError(f"unknown answer kind {kind!r}")
    return out


def _bf16(v: float) -> float:
    return float(np.asarray(v, dtype=ml_dtypes.bfloat16).astype(np.float64))


def _lower(ref: Reference, layer: int, rec: dict[str, Any], num_pes: int,
           noc_bw: float, sweep: bool, keys: Iterable[str]
           ) -> dict[str, Any]:
    want = ref.stats(layer, rec["dataflow"], num_pes, noc_bw, sweep=sweep)
    out = dict(rec)
    for k in keys:
        out[k] = _bf16(want[k])
    if "edp" in keys:
        out["edp"] = _bf16(out["energy_pj"] * out["runtime"])
    return out


def control(ref: Reference, answers: list[dict[str, Any]]
            ) -> list[dict[str, Any]]:
    """The answers as the reference computed in bfloat16 would give
    them: the same designs, each statistic rounded to bfloat16."""
    out = []
    for ans in answers:
        hw = ans["hw"]
        a = dict(ans)
        if ans["kind"] == "layer":
            a["best"] = _lower(ref, ans["layer"], ans["best"],
                               hw["num_pes"], hw["noc_bw"], False, STATS)
        elif ans["kind"] == "codse":
            a["mapping"] = _lower(ref, 0, ans["mapping"], hw["num_pes"],
                                  hw["noc_bw"], False, STATS)
            a["top"] = [_lower(ref, 0, d, d["num_pes"], d["noc_bw"], True,
                               STATS + COST_STATS) for d in ans["top"]]
        else:
            a["layers"] = [_lower(ref, i, lay, hw["num_pes"], hw["noc_bw"],
                                  False, ("runtime", "energy_pj"))
                           for i, lay in enumerate(ans["layers"])]
            a["runtime"] = _bf16(sum(_bf16(l["runtime"] + l["edge_cycles"])
                                     for l in a["layers"]))
            a["energy_pj"] = _bf16(sum(
                _bf16(l["energy_pj"] + l["edge_energy_pj"])
                for l in a["layers"]))
        out.append(a)
    return out


def verdict(numbers: dict[str, float], limits: dict[str, float]
            ) -> tuple[bool, dict[str, dict[str, float]]]:
    """Each compared number beside its limit; correct when every number
    is present, finite and within its limit."""
    table = {k: {"value": numbers.get(k, math.inf), "limit": lim}
             for k, lim in limits.items()}
    ok = all(math.isfinite(t["value"]) and t["value"] <= t["limit"]
             for t in table.values())
    return ok, table
