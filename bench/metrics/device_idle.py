"""Share of the traced window in which no operation ran on the device, in
percent: one less the union of operation intervals over the window."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["idle_share"] is None or tr["busy_s"] <= 0:
        return None
    return 100.0 * tr["idle_share"]
