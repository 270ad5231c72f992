"""Plain reference of the decisions a DSE query makes: which knob points
Algorithm 1's walk and the mapping ask the oracle for, the regions the
walk keeps, the throughput targets of the LP sweep and the cost of each
plan, and the point the mapping picks for every target (COSMOS,
arXiv:1912.10823, Sections 5 and 6).

The reference is fed the answers the query's oracle gave, one per knob
point it paid for (feasible or not, latency, area: the ledger's
records).  Measured walls have no other source; ``system_ref`` holds the
measured points' latency and area to the recorded walls and to
``area_bytes``.  From those answers the reference walks the design space
itself.  A point it asks for that the ledger lacks, or one the ledger
paid for that it never asks for, is a mismatch; so is a region, a
target count or a mapped point that differs.

Every component of the configurations here reads and writes its PLM, so
the walk takes Eq. (1)'s branch for the upper-left corner (Section 5).
The Eq. (1) caps are not recomputed: a request is keyed by its
component, ports, unrolls and whether it carries a cap.

The LP of Eq. (2) is solved here in its own form (one row per place of
the graph, one epigraph row per segment of each component's cost
envelope, times in microseconds and each component's cost in units of
its largest), by scipy's HiGHS.  An LP can have
several optimal plans, so the program's plan is held to the optimum's
cost, to the graph's throughput and to each component's latency range,
and not to one optimal vertex.

Nothing here imports the system under test.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import system_ref

Key = Tuple[str, int, int, bool]        # component, ports, unrolls, capped
INFEASIBLE = (False, math.inf, math.inf)


class Answers:
    """The oracle's answer per knob point, as the query's ledger paid for
    it; remembers every point asked for."""

    def __init__(self, records):
        self.table: Dict[Key, Tuple[bool, float, float]] = {}
        self.duplicates = 0
        for r in records:
            key = (r.component, r.ports, r.unrolls, r.max_states is not None)
            self.duplicates += key in self.table
            self.table[key] = (r.feasible, r.lam, r.area)
        self.asked = set()

    def ask(self, component: str, ports: int, unrolls: int,
            capped: bool) -> Tuple[bool, float, float]:
        key = (component, ports, unrolls, capped)
        self.asked.add(key)
        return self.table.get(key, INFEASIBLE)

    def mismatches(self) -> int:
        return self.duplicates + len(self.asked ^ set(self.table))


@dataclass(frozen=True)
class Region:
    ports: int
    mu_min: int
    mu_max: int
    lam_max: float
    area_min: float
    lam_min: float
    area_max: float


def ports_ladder(lo: int, hi: int) -> List[int]:
    """The powers of two from ``lo`` (rounded up) to ``hi``."""
    p = 1
    while p < lo:
        p *= 2
    out = []
    while p <= hi:
        out.append(p)
        p *= 2
    return out


def walk(ans: Answers, component: str, knobs: Dict) -> List[Region]:
    """Algorithm 1 for one component: per ports rung, the lower-right
    corner (unrolls = ports), then the upper-left corner (the largest
    unrolls, walking down, whose capped synthesis is feasible); a region
    whose fast corner is no faster than a kept one is dropped
    (Section 7.2)."""
    regions: List[Region] = []
    best = math.inf
    for ports in ports_ladder(knobs["min_ports"], knobs["max_ports"]):
        mu_min = max(1, ports)
        ok, lam_max, area_min = ans.ask(component, ports, mu_min, False)
        if not ok:
            continue
        mu_max, lam_min, area_max = mu_min, lam_max, area_min
        for unrolls in range(knobs["max_unrolls"], mu_min, -1):
            ok, lam, area = ans.ask(component, ports, unrolls, True)
            if ok:
                mu_max, lam_min, area_max = unrolls, lam, area
                break
        region = Region(ports, mu_min, mu_max, lam_max, area_min,
                        lam_min, area_max)
        if lam_min < best * (1.0 - 1e-9) or not regions:
            regions.append(region)
            best = min(best, lam_min)
    return regions


# ----------------------------------------------------------------------
# planning
# ----------------------------------------------------------------------
def envelope(points: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """(slope, intercept) of each segment of the lower convex hull of
    (latency, area) points: the cost a component is planned at is the
    largest of these lines (Section 6.1)."""
    pts = sorted(set(points))
    hull: List[Tuple[float, float]] = []
    for p in pts:
        while len(hull) >= 2:
            (x1, y1), (x2, y2) = hull[-2], hull[-1]
            if (y2 - y1) * (p[0] - x1) >= (p[1] - y1) * (x2 - x1):
                hull.pop()
            else:
                break
        hull.append(p)
    segs = [((y2 - y1) / (x2 - x1), y1 - (y2 - y1) / (x2 - x1) * x1)
            for (x1, y1), (x2, y2) in zip(hull, hull[1:]) if x2 != x1]
    return segs or [(0.0, min(y for _, y in pts))]


@dataclass(frozen=True)
class Model:
    lo: float
    hi: float
    segs: Tuple[Tuple[float, float], ...]

    def cost(self, lam: float) -> float:
        return max(a * lam + b for a, b in self.segs)


def models(config, regions: Dict[str, List[Region]]) -> Dict[str, Model]:
    out = {n: Model(lam, lam, ((0.0, 0.0),))
           for n, lam in config["fixed"].items()}
    for name, regs in regions.items():
        pts = [(r.lam_max, r.area_min) for r in regs] + \
              [(r.lam_min, r.area_max) for r in regs]
        out[name] = Model(min(r.lam_min for r in regs),
                          max(r.lam_max for r in regs),
                          tuple(envelope(pts)))
    return out


def theta_bounds(config, mdl: Dict[str, Model]) -> Tuple[float, float]:
    """The throughput of the graph with every component at its slowest,
    and at its fastest (Section 6.1)."""
    tmg = config["tmg"]
    return tuple(system_ref.throughput(tmg["transitions"], tmg["places"],
                                       {n: getattr(m, end)
                                        for n, m in mdl.items()})
                 for end in ("hi", "lo"))


def sweep(config, mdl: Dict[str, Model]) -> List[Tuple[float, float]]:
    """(throughput target, least cost) of each plan of the sweep: from
    the slowest graph's throughput, each target (1 + delta) times the one
    before, up to the fastest graph's, which always ends the sweep; a
    target no plan meets is left out (Section 6.1)."""
    lo, hi = theta_bounds(config, mdl)
    out, theta = [], lo
    while theta < hi * (1.0 + 1e-9):
        out.append((theta, lp_cost(config, mdl, theta)))
        theta *= 1.0 + float(config["delta"])
    out = [p for p in out if p[1] < math.inf]
    if not out or abs(out[-1][0] - hi) / hi > 1e-9:
        out.append((hi, lp_cost(config, mdl, hi)))
    return [p for p in out if p[1] < math.inf]


def lp_cost(config, mdl: Dict[str, Model], theta: float) -> float:
    """The least total cost of Eq. (2) at ``theta``: latencies within
    each component's range such that every place's consumer can fire
    one producer latency after its producer, less the place's tokens
    times the period."""
    import numpy as np
    from scipy.optimize import linprog
    names = list(config["tmg"]["transitions"])
    n = len(names)
    at = {t: i for i, t in enumerate(names)}
    us = 1e6                                        # seconds -> microseconds
    unit = {t: mdl[t].cost(mdl[t].lo) or 1.0 for t in names}   # per cost
    total = sum(unit.values())
    rows, rhs = [], []
    for src, dst, tokens in config["tmg"]["places"]:
        row = np.zeros(3 * n)                       # sigma, tau, cost
        row[at[src]] += 1.0
        row[at[dst]] -= 1.0
        row[n + at[src]] += 1.0
        rows.append(row)
        rhs.append(float(tokens) * us / theta)
    for name, m in mdl.items():
        for a, b in m.segs:
            row = np.zeros(3 * n)
            row[n + at[name]] = a / us / unit[name]
            row[2 * n + at[name]] = -1.0
            rows.append(row)
            rhs.append(-b / unit[name])
    bounds = ([(0.0, 0.0)] + [(None, None)] * (n - 1)
              + [(mdl[t].lo * us, mdl[t].hi * us) for t in names]
              + [(None, None)] * n)
    c = np.zeros(3 * n)
    c[2 * n:] = [unit[t] / total for t in names]
    res = linprog(c, A_ub=np.array(rows), b_ub=np.array(rhs), bounds=bounds,
                  method="highs")
    if not res.success:
        return math.inf
    tau = res.x[n:2 * n] / us
    return float(sum(mdl[t].cost(float(tau[at[t]])) for t in names))


def plan_gaps(config, mdl: Dict[str, Model], optimum: float, theta: float,
              cost: float, targets: Dict[str, float]) -> float:
    """The largest relative gap of one plan from the reference: its cost
    against the LP's ``optimum`` and against the envelope at its own
    targets, the graph's throughput under its targets against ``theta``,
    and each target against its component's range."""
    tmg = config["tmg"]
    gap = max(system_ref.rel_gap(cost, optimum),
              system_ref.rel_gap(cost, sum(mdl[n].cost(targets[n])
                                           for n in mdl)))
    cycle = system_ref.max_cycle_mean(tmg["transitions"], tmg["places"],
                                      targets)
    gap = max(gap, theta * cycle - 1.0)
    for n, m in mdl.items():
        lam = targets[n]
        gap = max(gap, (m.lo - lam) / m.lo, (lam - m.hi) / m.hi)
    return gap


# ----------------------------------------------------------------------
# mapping
# ----------------------------------------------------------------------
def phi(lam: float, r: Region) -> float:
    """Eq. (5): the unrolls at which the region's Amdahl curve reaches
    latency ``lam``."""
    if r.lam_max <= r.lam_min:
        return float(r.mu_max)
    num = ((r.lam_min * r.lam_max * r.mu_max + lam * r.lam_max * r.mu_min)
           - (r.lam_min * r.lam_max * r.mu_min + lam * r.lam_min * r.mu_max))
    return num / (lam * (r.lam_max - r.lam_min))


def map_one(ans: Answers, component: str, regions: Sequence[Region],
            lam: float, bumps: int = 4) -> Tuple[int, int]:
    """(ports, unrolls) the mapping picks for latency target ``lam``
    (Section 6.2): the first region, fewest ports first, whose latency
    range holds the target; Eq. (5)'s unrolls, rounded up, then up to
    ``bumps`` more until the latency meets the target (a point that
    misses it by at most 25% inside the region is kept); otherwise the
    slowest point of the next faster region, or the fastest point of
    all."""
    regs = sorted(regions, key=lambda r: r.lam_max, reverse=True)

    def corner(r: Region, fast: bool) -> Tuple[int, int]:
        ans.ask(component, r.ports, r.mu_max if fast else r.mu_min, fast)
        return r.ports, r.mu_max if fast else r.mu_min

    def fallback() -> Tuple[int, int]:
        faster = [r for r in regs if r.lam_max < lam]
        if faster:
            return corner(max(faster, key=lambda r: r.lam_max), False)
        return corner(min(regs, key=lambda r: r.lam_min), True)

    inside = [r for r in regs if r.lam_min - 1e-12 <= lam <= r.lam_max + 1e-12]
    if not inside:
        if lam > regs[0].lam_max:
            return corner(regs[0], False)
        return fallback()
    r = inside[0]
    mu = max(r.mu_min, min(r.mu_max, int(math.ceil(phi(lam, r)))))
    last = None
    for bump in range(bumps + 1):
        mu_try = min(r.mu_max, mu + bump)
        ok, got, _ = ans.ask(component, r.ports, mu_try, True)
        if ok:
            last = (mu_try, got)
            if got <= lam * (1.0 + 1e-9):
                return r.ports, mu_try
        if mu_try == r.mu_max:
            break
    if last is not None and last[1] <= r.lam_max + 1e-12 \
            and last[1] <= lam * 1.25:
        return r.ports, last[0]
    return fallback()


# ----------------------------------------------------------------------
def check_query(config, result, ledger) -> Tuple[float, int]:
    """(largest relative gap of the plans, count of mismatches) between
    one query's decisions and the reference's."""
    ans = Answers(ledger.records)
    bad = 0
    regions: Dict[str, List[Region]] = {}
    for name, knobs in config["knobs"].items():
        regions[name] = walk(ans, name, knobs)
        got = result.characterizations.get(name)
        have = [] if got is None else [
            Region(r.ports, r.mu_min, r.mu_max, r.lam_max, r.area_min,
                   r.lam_min, r.area_max) for r in got.regions]
        bad += have != regions[name]
        if not regions[name]:
            return math.inf, bad + 1
    mdl = models(config, regions)
    lo, hi = theta_bounds(config, mdl)
    plans = sweep(config, mdl)
    bad += len(result.planned) != len(plans)
    bad += len(result.mapped) != len(result.planned)
    gap = max(system_ref.rel_gap(result.theta_min, lo),
              system_ref.rel_gap(result.theta_max, hi))
    for (want, optimum), pt, sp in zip(plans, result.planned, result.mapped):
        gap = max(gap, system_ref.rel_gap(pt.theta, want),
                  plan_gaps(config, mdl, optimum, pt.theta, pt.cost,
                            pt.lam_targets))
        picked = {o.component: (o.synthesis.ports, o.synthesis.unrolls)
                  for o in sp.outcomes}
        for name in config["knobs"]:
            bad += picked.get(name) != map_one(ans, name, regions[name],
                                               pt.lam_targets[name])
    return gap, bad + ans.mismatches()
