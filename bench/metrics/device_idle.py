"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals / window).  It reads every
``device_idle.<cell>`` metric that has no reader of its own."""


def read(obs: dict):
    t = obs["trace"]
    if not t["devices"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
