"""Percent of the traced window in which no operation runs on a chip,
on the idlest chip."""


def read(run):
    busy, window = run.trace["busy_per_device_s"], run.trace["window_s"]
    if not busy or window <= 0:
        return None
    return 100.0 * max(1.0 - b / window for b in busy)
