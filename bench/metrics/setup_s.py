"""Seconds from process start to the window's opening: start-up, weights,
the deployed image, compiling or loading every program, warming up, and in
a backlog cell filling the slots."""


def read(ctx):
    return ctx.setup_s
