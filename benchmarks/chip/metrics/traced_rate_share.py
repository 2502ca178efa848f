"""The profiler's cost: frames a second served in a traced run's traced
slice, as a share of those served in the untraced part of the window
before it, in %.  The slice's device numbers describe a host slowed by
this much.  Host clock."""


def read(rec):
    if rec["traced_s"] <= 0 or rec["untraced_s"] <= 0 \
            or not rec["untraced_frames"]:
        return None
    traced = rec["traced_frames"] / rec["traced_s"]
    untraced = rec["untraced_frames"] / rec["untraced_s"]
    return 100.0 * traced / untraced
