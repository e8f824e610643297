"""Device busy time per super-step in the traced sub-window, in us."""


def read(ctx):
    tr, traced = ctx["trace"], ctx["traced"]
    if tr is None or not traced or not traced["batches"]:
        return None
    return tr["busy_s"] / traced["batches"] * 1e6
