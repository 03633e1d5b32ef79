"""Share of the traced window in which no operation ran on the device."""


def read(ctx, how):
    d = ctx["device"]
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])
