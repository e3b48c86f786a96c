"""One small reader per kind of metric.  Each module has
``reduce(ctx, **args)`` and returns a number, or None when there is
nothing to read (the harness then leaves the metric out of the line).
``ctx`` is described in perfbench/README.md."""
