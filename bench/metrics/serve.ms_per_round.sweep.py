"""Milliseconds per engine round: the traced window over the rounds the
engine completed in it (a mean over the window)."""


def read(readings):
    red, counts = readings.get("trace"), readings["counts"]
    if red is None or not counts.get("rounds"):
        return None
    return 1e3 * red.window_s / counts["rounds"]
