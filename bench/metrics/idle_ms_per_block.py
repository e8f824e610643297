"""Device idle time in the traced sub-window per arrival block the
engine absorbed in it (rows absorbed / block size), in ms."""


def read(ctx):
    tr, traced = ctx["trace"], ctx["traced"]
    if tr is None or not traced or not traced.get("blocks"):
        return None
    return tr["idle_s"] / traced["blocks"] * 1e3
