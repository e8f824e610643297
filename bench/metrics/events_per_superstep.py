"""Committed events per super-step over the measured window: a count
from the run's own counters, which repeats exactly for one seed."""


def read(ctx):
    w = ctx["window"]
    return w["events"] / w["batches"] if w["batches"] else None
