"""Share (%) of the traced window in which no operation ran on the
device, averaged over the chips used."""


def reduce(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
