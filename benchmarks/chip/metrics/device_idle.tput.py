"""Device layer: share of the traced window in which no operation ran
on the device (1 - the union of op intervals over the window), mean over
the chips used, in %."""


def read(rec):
    tr = rec["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
