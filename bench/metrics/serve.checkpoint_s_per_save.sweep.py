"""Seconds one checkpoint holds the engine's round: the summed time of
the program's ``serve.checkpoint`` spans in the traced window over
their number."""


def read(readings):
    ckpt = (readings.get("spans") or {}).get("serve.checkpoint")
    return ckpt["total_s"] / ckpt["count"] if ckpt else None
