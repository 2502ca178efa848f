"""Frames whose label reached the harness inside the window, over the
window's seconds (all chips together).  Host clock."""


def read(rec):
    return rec["frames_done"] / rec["window_s"]
