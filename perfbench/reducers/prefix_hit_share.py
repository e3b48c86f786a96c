"""Share (%) of the window's prompt tokens that the prefix cache served:
growth of ``prefix_cache.hit_tokens`` over the prompt tokens of requests
whose first token arrived inside the window."""
from .stats_delta import reduce as delta


def reduce(ctx):
    hits = delta(ctx, "engine.scheduler.prefix_cache.hit_tokens")
    prompts = ctx["window_prompt_tokens"]
    if hits is None or not prompts:
        return None
    return 100.0 * hits / prompts
