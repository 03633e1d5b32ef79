"""The rate at which a mesh step exchanged its gradient, GB/s: the bytes a
step handed to its ``psum``s (the rise of the program's counter over the
untraced part of the window, over the steps taken there) over the device time
a step of the operations under the exchange's scope in the traced part, on
the first chip. A program without the counter (or a step without the scope:
one device) gives None.

how: {"counter": name of the counter,
      "module", "any"[, "none"]: as readers/scope_time.py takes them}
"""

from readers import scope_time


def bytes_a_step(ctx, how):
    before, after = ctx["telemetry"]

    def total(snap):
        return sum(c["value"] for c in snap["counters"]
                   if c["name"] == how["counter"])
    rise, steps = total(after) - total(before), ctx["plain"].steps
    return rise / steps if rise > 0 and steps else None


def read(ctx, how):
    size, ms = bytes_a_step(ctx, how), scope_time.read(ctx, how)
    if size is None or not ms:
        return None
    return size / (ms * 1e-3) / 1e9
