"""Device idle share of the traced sub-window: 1 - (union of the
device's operation intervals / window), averaged over the chips."""


def read(ctx):
    tr = ctx["trace"]
    return None if tr is None else tr["idle_share"]
