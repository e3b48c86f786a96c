"""Growth of a ``/stats`` counter (dotted path) between the window's two
ends."""


def dig(obj, path):
    for key in path.split("."):
        if not isinstance(obj, dict) or key not in obj:
            return None
        obj = obj[key]
    return obj


def reduce(ctx, path):
    a = dig(ctx["stats"].get("open"), path)
    b = dig(ctx["stats"].get("close"), path)
    if a is None or b is None:
        return None
    return float(b - a)
