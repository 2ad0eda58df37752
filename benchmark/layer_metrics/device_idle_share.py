"""Share of the traced window in which no operation ran on the fullest
device, in %."""


def read(trace, facts):
    device = trace["fullest"]
    return 100.0 * (1.0 - device["busy_s"] / device["window_s"])
