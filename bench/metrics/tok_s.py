"""Tokens the engine returned to the host inside the window (first tokens
and decode tokens) over the window's length."""


def read(ctx):
    return ctx.e2e["tok_s"] if ctx.e2e["tokens"] else None
