"""The whole dispatch: binary operations of the frames served in the
traced run's untraced part (the window before its traced slice) over
that part's seconds, the chips and the int8 peak, in %.  Host clock."""


def read(rec):
    if rec["untraced_s"] <= 0 or not rec["untraced_ops"]:
        return None
    return 100.0 * rec["untraced_ops"] / (rec["untraced_s"] * rec["chips"]
                                          * rec["peaks"]["int8_ops_per_s"])
