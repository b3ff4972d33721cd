"""Operations per second of kernel time: the program's operations per
word-step (``kernel.build``'s ``ops_per_word_step``) times the window's
words x CA steps, over the summed device time of the kernel's events,
per chip; on several chips the slowest chip's.  The numerator of the
kernel's VPU roofline.  None without the count or the trace."""
from bench import trace

WORD = 32                                  # sites per packed word


def read(readings):
    red, counts = readings.get("trace"), readings.get("counts") or {}
    ops = counts.get("ops_per_word_step")
    if red is None or not red.devices or ops is None:
        return None
    per_chip = counts["site_updates"] / WORD / len(red.devices)
    rates = []
    for dev in red.devices:
        busy = trace.kernel_seconds(dev, trace.KERNEL)
        if busy <= 0:
            return None
        rates.append(ops * per_chip / busy / 1e9)
    return min(rates)
