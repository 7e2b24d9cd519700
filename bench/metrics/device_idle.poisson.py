"""device_idle.poisson: the device's idle share of the traced window in the
poisson cells, 100 * (1 - busy / window), where busy is the union of
device-operation intervals (device trace, ``xplane.py``)."""


def read(run):
    red = run["trace"]
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
