"""Share of the traced window in which a collective runs on the fullest
device and no other operation does."""


def read(ctx):
    t = ctx.trace or {}
    if t.get("devices", 0) < 2 or not t.get("collective_s_fullest"):
        return None
    return 100.0 * t["exposed_collective_s_fullest"] / t["window_s"]
