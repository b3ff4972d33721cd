"""Share of the traced window in which a collective (the halo exchange)
runs on a chip with no other operation beside it: the exchange the
overlap split leaves bare, on the chip where it is largest."""
from bench import trace


def read(readings):
    red = readings.get("trace")
    if red is None or not any(trace.COLLECTIVE.search(name)
                              for dev in red.devices
                              for name, _, _ in dev.ops):
        return None
    return max(trace.length(dev.exposed_collectives())
               for dev in red.devices) / 1e9 / red.window_s
