"""Site updates per second of kernel time: the window's site updates
over the summed device time of the kernel's events, per chip; on several
chips the slowest chip's."""
from bench import trace


def read(readings):
    red, counts = readings.get("trace"), readings["counts"]
    if red is None or not red.devices:
        return None
    per_chip = counts["site_updates"] / len(red.devices)
    rates = []
    for dev in red.devices:
        busy = trace.kernel_seconds(dev, trace.KERNEL)
        if busy <= 0:
            return None
        rates.append(per_chip / busy / 1e9)
    return min(rates)
