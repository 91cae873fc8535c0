"""Device: the share of the traced window, in percent, in which no
operation ran on the device (1 - busy / window, from the profiler trace)."""


def read(data):
    tr = data.get("trace")
    if not tr or tr["window_s"] <= 0 or tr["devices"] == 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
