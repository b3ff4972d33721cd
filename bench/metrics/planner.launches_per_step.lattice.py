"""Kernel launches per CA step: the kernel's events in the traced window
over the CA steps the window completed (on several chips, the busiest
chip's count)."""
from bench import trace


def read(readings):
    red, counts = readings.get("trace"), readings["counts"]
    if red is None or not red.devices or not counts["ca_steps"]:
        return None
    launches = max(len(dev.events(trace.KERNEL)) for dev in red.devices)
    return launches / counts["ca_steps"] if launches else None
