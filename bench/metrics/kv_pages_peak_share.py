"""Peak live share of the KV page pool (``pager.occupancy()
["occupancy_live"]``) over the window's rounds, in percent."""


def read(run):
    peak = max(run.occupancy, default=0.0)
    return 100.0 * peak if peak > 0 else None
