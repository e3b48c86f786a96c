"""Tokens per second per chip over the whole window: ``output`` counts
the tokens whose SSE chunks ARRIVED inside the window, whichever request
they belong to; ``prompt`` the prompt tokens of requests whose first
token arrived inside it."""


def reduce(ctx, what):
    key = {"output": "window_tokens", "prompt": "window_prompt_tokens"}[what]
    return ctx[key] / ctx["seconds"] / ctx["chips"]
