"""Queries answered per second over the window (client side): every
``ok`` query counts by the share of its life inside the window
(``Run.window_share``), so one still running at the close counts in part."""


def read(run):
    return sum(run.window_share(r) for r in run.records) / run.seconds
