"""Matches answered to the clients per second over the window (client
side): every ``ok`` query's count, as its terminal status gives it (the
mappings streamed, where the mix streams them), counts by the share of
its life inside the window (``Run.window_share``)."""


def read(run):
    return sum(run.window_share(r) * (r.count or 0)
               for r in run.records) / run.seconds
