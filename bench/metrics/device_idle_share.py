"""device_idle_share (device trace): 1 - the union of the device's
operation intervals over the traced window, averaged over the chips, in
percent."""


def read(rec):
    t = rec.get("trace")
    if not t or not t["devices"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
