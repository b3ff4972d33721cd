"""Seconds the engine spends on one placement of a job in a lane: the
summed time of the program's ``serve.place`` spans (build on the host,
set into the lane on the device) in the traced window over their
number.  It counts placements, so a parked job placed again, or a job
rebuilt after a rollback, counts once per placement."""


def read(readings):
    place = (readings.get("spans") or {}).get("serve.place")
    return place["total_s"] / place["count"] if place else None
