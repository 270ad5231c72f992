"""Plain reference of the system-level answers of a DSE query.

A query returns mapped system points (a throughput theta and a cost per
point), the Pareto front over them, and the ledger of oracle invocations
that paid for them.  This module recomputes each from the configuration's
timed marked graph and the per-component answers the oracle gave:

  * theta of a mapped point: the reciprocal of the largest cycle mean
    D_k / N_k of the graph (Ramamoorthy and Ho, 1980), where D_k sums the
    effective latencies of the cycle's transitions and N_k its tokens;
  * cost of a mapped point: the sum of its components' areas (no memory
    sharing: the configurations run without the PLM planner);
  * the front: the mapped points no other point dominates under
    maximum throughput and minimum cost;
  * each measured point's latency (its recorded wall over its ports) and
    area (``area_bytes`` of the configuration's reference).

Nothing here imports the system under test.
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Sequence, Tuple


def max_cycle_mean(transitions: Sequence[str], places: Sequence[Sequence],
                   delays: Dict[str, float]) -> float:
    """max over simple cycles of (sum of delays) / (tokens); a cycle with
    no token is a deadlock and gives +inf.  ``places`` holds
    ``[src, dst, tokens]`` edges; cycles are enumerated from their
    lowest-numbered transition (Tiernan's search)."""
    order = {t: i for i, t in enumerate(transitions)}
    out: Dict[str, List[Tuple[str, int]]] = {t: [] for t in transitions}
    for src, dst, tokens in places:
        out[src].append((dst, int(tokens)))
    worst = 0.0
    for start in transitions:
        s = order[start]
        stack = [(start, 0.0, 0, (start,))]
        while stack:
            node, d, n, path = stack.pop()
            d_here = d + delays[node]
            for dst, tokens in out[node]:
                if dst == start:
                    if n + tokens == 0:
                        return math.inf
                    worst = max(worst, d_here / (n + tokens))
                elif order[dst] > s and dst not in path:
                    stack.append((dst, d_here, n + tokens, path + (dst,)))
    return worst


def throughput(transitions, places, delays) -> float:
    mct = max_cycle_mean(transitions, places, delays)
    return math.inf if mct == 0.0 else 1.0 / mct


def pareto(points: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Unique (theta, cost) pairs that no other pair dominates (theta at
    least as high and cost at least as low, one of them strictly)."""
    pts = sorted(set(points))
    return [p for p in pts
            if not any(q[0] >= p[0] and q[1] <= p[1] and q != p for q in pts)]


def rel_gap(value: float, ref: float) -> float:
    if value == ref:
        return 0.0
    if not (math.isfinite(value) and math.isfinite(ref)):
        return math.inf
    return abs(value - ref) / max(abs(ref), 1e-300)


def check_query(config, area_bytes, result, ledger, walls) -> Tuple[float, int]:
    """(largest relative gap, count of mismatches) between one query's
    answers and the reference.

    ``result`` is the query's result (mapped points with their outcomes,
    the front, the invocation totals); ``ledger`` its invocation ledger
    (counts and records); ``walls`` maps (component, ports, unrolls) to
    the wall the oracle recorded for that measured point.  Components
    priced by the analytical fallback are checked for their part in
    theta, cost and the ledger, not for their own latency and area."""
    gap, bad = 0.0, 0
    kernels = config["kernels"]
    tmg = config["tmg"]
    # ledger: counters agree with the records of the calls paid for
    counted: Dict[str, int] = {}
    failed: Dict[str, int] = {}
    seen = set()
    by_key = {}
    for r in ledger.records:
        counted[r.component] = counted.get(r.component, 0) + 1
        if not r.feasible:
            failed[r.component] = failed.get(r.component, 0) + 1
        key = (r.component, r.unrolls, r.ports, r.max_states, r.tile)
        bad += key in seen
        seen.add(key)
        by_key.setdefault((r.component, r.unrolls, r.ports, r.tile), []).append(r)
        if r.component in kernels and r.feasible:
            wall = walls.get((r.component, r.ports, r.unrolls))
            if wall is None:
                bad += 1
                continue
            gap = max(gap, rel_gap(r.lam, wall / r.ports),
                      rel_gap(r.area, area_bytes(config, r.component,
                                                 r.ports, r.unrolls)))
    for comp in set(counted) | set(ledger.invocations):
        bad += counted.get(comp, 0) != ledger.invocations.get(comp, 0)
        bad += failed.get(comp, 0) != ledger.failed.get(comp, 0)
    bad += dict(result.invocations) != dict(ledger.invocations)
    # mapped points: theta from the graph, cost as the sum of areas
    for m in result.mapped:
        delays = dict(config["fixed"])
        cost = 0.0
        for o in m.outcomes:
            s = o.synthesis
            delays[o.component] = s.lam
            cost += s.area
            paid = by_key.get((o.component, s.unrolls, s.ports, s.tile), [])
            bad += not any(r.lam == s.lam and r.area == s.area for r in paid)
        bad += set(delays) != set(tmg["transitions"])
        if set(delays) == set(tmg["transitions"]):
            gap = max(gap, rel_gap(m.theta_actual, throughput(
                tmg["transitions"], tmg["places"], delays)))
        gap = max(gap, rel_gap(m.cost_actual, cost))
    # the front over the mapped points
    front = sorted((p.perf, p.cost) for p in result.pareto())
    ref = pareto((m.theta_actual, m.cost_actual) for m in result.mapped)
    bad += len(set(front) ^ set(ref))
    return gap, bad
