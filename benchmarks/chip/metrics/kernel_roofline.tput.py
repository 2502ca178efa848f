"""Kernels layer: the least time the served work needs on the chip (the
larger of its binary operations over the int8 peak and its bytes over
HBM bandwidth, ``work.py``) over the summed device time of the Mosaic
kernels (``tpu_custom_call``) in the traced window, in %."""

import work


def read(rec):
    tr = rec["trace"]
    if not tr or tr["kernel_s"] <= 0 or not rec["ops"]:
        return None
    least, _bound = work.least_time_s(rec["ops"], rec["bytes"], rec["peaks"])
    return 100.0 * least / tr["kernel_s"]
