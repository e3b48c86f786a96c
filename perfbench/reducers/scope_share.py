"""Share (%) of device busy time spent in operations traced under one of
the named scopes ``scopes`` (``jax.named_scope``: HLO metadata, which a
device event does not carry and ``perfbench.trace`` does not read).
``perfbench.trace_scopes`` reads the same ``.xplane.pb`` again, in a CPU
process of its own, takes each operation's traced name from the module's
HLO in the profile's metadata plane, and sums SELF time by scope.  None
where there is no trace, the profile could not be read, or the trace has
no operation under such a scope (a program without that sub-block)."""
import json
import os
import subprocess
import sys


def summary(ctx):
    """``{"busy_s": ..., "scope_seconds": {scope: s}}`` for the run's
    trace and ``scopes``; cached in the context."""
    from ..run import TMP
    from ..server import ROOT

    trace_dir = os.path.join(TMP, "trace")
    if not ctx.get("trace") or not os.path.isdir(trace_dir):
        return None
    out = os.path.join(TMP, "trace_scopes.json")
    if not os.path.exists(out):
        proc = subprocess.run(
            [sys.executable, "-m", "perfbench.trace_scopes", trace_dir, out],
            cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=600,
        )
        if proc.returncode != 0:
            return None
    with open(out) as fh:
        return json.load(fh)


def reduce(ctx, scopes):
    data = summary(ctx)
    if not data or data["busy_s"] <= 0:
        return None
    spent = sum(s for name, s in data["scope_seconds"].items()
                if any(scope in name.split("/") for scope in scopes))
    return 100.0 * spent / data["busy_s"] if spent > 0 else None
