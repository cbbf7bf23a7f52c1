"""Share of the traced window in which no operation ran on device 0:
1 - union of the device-op intervals / window."""


def read(metric: dict, ctx: dict):
    tr = ctx["trace"]
    if tr is None:
        return None
    return 100.0 * (1.0 - tr["busy_s_device0"] / tr["window_s"])
