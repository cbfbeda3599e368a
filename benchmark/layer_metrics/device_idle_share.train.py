"""Device: 1 - union of device-operation intervals over the traced window."""


def read(facts):
    tr = facts["trace"]
    if not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
