"""Share of the engine's round time spent on bookkeeping: the summed
serve.admit, serve.audit, serve.frames, serve.retire and
serve.checkpoint span time over the summed serve.round span time, from
the program's own spans in the traced window."""

BOOKKEEPING = ("serve.admit", "serve.audit", "serve.frames", "serve.retire",
               "serve.checkpoint")


def read(readings):
    spans = readings.get("spans")
    if not spans or not spans.get("serve.round", {}).get("total_s"):
        return None
    book = sum(spans.get(name, {}).get("total_s", 0.0)
               for name in BOOKKEEPING)
    return book / spans["serve.round"]["total_s"]
