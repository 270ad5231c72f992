"""Kernel points the oracle measured in the window (compiled and timed,
or refused by the compiler), per second of the window."""


def read(run):
    return len(run.in_window()) / run.seconds
