"""Seconds from the start of the process to the start of the window:
imports, device start-up, seeded inputs, warm-up points, and the
traffic's set-up queries."""


def read(run):
    return run.setup_s
