"""Nearest-rank p95 of job latency, from a job's due time to its final
frame, over every job due in the window; a job shed, refused or not
finished counts as never finishing (host clock)."""


def read(readings):
    return readings["counts"].get("job_latency_p95_s")
