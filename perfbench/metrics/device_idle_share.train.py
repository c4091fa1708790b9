"""Percent of the traced window in which no operation ran on the device
(1 - busy / window, averaged over the chips)."""


def read(run):
    if run.reduced is None or run.reduced.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.reduced.busy_s / run.reduced.window_s)
