"""Share (%) of the session's phases (characterize, plan, map) spent
outside the oracle's per-point calls: the host's DSE work (the
Algorithm 1 walk, the LP sweep, mapping).  Read from the program's
WallClock spans over every query of the run."""

PHASES = ("session.characterize", "session.plan", "session.map")


def read(run):
    if run.tracer is None:
        return None
    spans = run.tracer.spans()
    phase = sum(s.end - s.start for s in spans if s.name in PHASES)
    tool = sum(s.end - s.start for s in spans if s.name == "tool.point")
    if phase <= 0:
        return None
    return 100.0 * (phase - tool) / phase
