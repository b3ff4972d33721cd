"""Nearest-rank p95 of a job's wait in the engine's queue, from its
submission to its first placement in a lane: the program's
``serve.queue`` intervals of the jobs placed in the traced window."""


def read(readings):
    queue = (readings.get("spans") or {}).get("serve.queue")
    return queue["p95_s"] if queue else None
