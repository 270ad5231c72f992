"""Median, over the kernel points measured in the run, of the wall
the oracle recorded per launch (best of its timed reps), in ms."""

import statistics


def read(run):
    walls = []
    for q in run.queries:
        for store in q.oracle.measurements.stores():
            walls.extend(store.entries.values())
    return 1e3 * statistics.median(walls) if walls else None
