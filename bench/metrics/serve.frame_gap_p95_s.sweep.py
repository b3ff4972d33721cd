"""p95 of the gaps between consecutive frames of one job, over every job
due in the window (frame walls of the engine's frame log, host clock)."""


def read(readings):
    return readings["counts"].get("frame_gap_p95_s")
