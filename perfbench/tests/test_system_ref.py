"""The system and DSE references against hand-worked graphs and fronts,
and against the program on its committed recording."""

import math

import dse_ref
import system_ref


def test_pipeline_cycle_mean_by_hand():
    # two stages, one buffer: the cycle a->b->a carries one token, so the
    # period is lam_a + lam_b; self loops give lam_a and lam_b alone
    ts = ["a", "b"]
    places = [["a", "b", 0], ["b", "a", 1], ["a", "a", 1], ["b", "b", 1]]
    assert system_ref.max_cycle_mean(ts, places, {"a": 2.0, "b": 3.0}) == 5.0
    assert system_ref.throughput(ts, places, {"a": 2.0, "b": 3.0}) == 0.2


def test_token_free_cycle_deadlocks():
    ts = ["a", "b"]
    places = [["a", "b", 0], ["b", "a", 0]]
    assert system_ref.max_cycle_mean(ts, places, {"a": 1.0, "b": 1.0}) == math.inf


def test_feedback_loop_with_more_tokens():
    # a->b->c->a with 2 tokens on the loop: (1+2+3)/2 = 3 > any self loop
    ts = ["a", "b", "c"]
    places = [["a", "b", 0], ["b", "c", 0], ["c", "a", 2],
              ["a", "a", 1], ["b", "b", 1], ["c", "c", 1]]
    assert system_ref.max_cycle_mean(ts, places,
                                     {"a": 1.0, "b": 2.0, "c": 3.0}) == 3.0


def test_pareto_keeps_unique_undominated_points():
    pts = [(1.0, 5.0), (2.0, 5.0), (2.0, 5.0), (3.0, 9.0), (2.5, 9.5)]
    assert system_ref.pareto(pts) == [(2.0, 5.0), (3.0, 9.0)]


def test_matches_the_program_on_the_wami_graph():
    import json
    import os
    from repro.apps.wami.pipeline import wami_tmg
    cfg = json.load(open(os.path.join(os.path.dirname(__file__), "..",
                                      "configs", "wami-perfect512-t128.json")))
    tmg = cfg["tmg"]
    delays = {t: 1e-4 * (i + 1) for i, t in enumerate(tmg["transitions"])}
    ref = system_ref.throughput(tmg["transitions"], tmg["places"], delays)
    assert abs(ref - wami_tmg().throughput(delays)) <= 1e-12 * ref


def test_dse_reference_agrees_with_the_committed_replay():
    # the committed WAMI recording drives a whole query without a chip:
    # the reference asks for the same knob points, keeps the same
    # regions, plans the same targets and maps the same points
    import json
    import os
    from repro.core.registry import build_session
    cfg = json.load(open(os.path.join(os.path.dirname(__file__), "..",
                                      "configs", "wami-perfect512-t128.json")))
    session = build_session("wami", "pallas", delta=cfg["delta"])
    gap, bad = dse_ref.check_query(cfg, session.run(), session.ledger)
    assert bad == 0
    assert gap <= cfg["limits"]["plan_gap"]


def test_envelope_is_the_lower_hull():
    # (2, 7) lies above the segment from (1, 8) to (4, 2) and drops out
    segs = dse_ref.envelope([(1.0, 8.0), (2.0, 7.0), (4.0, 2.0)])
    assert segs == [(-2.0, 10.0)]
