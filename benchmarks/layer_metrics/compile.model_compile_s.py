"""Seconds of `FFModel.compile(...)` less the search, plus the first
train-step call (which compiles the step or fetches it from the cache)."""


def read(ctx):
    c = ctx["counters"]
    return c["ff_compile_s"] - c["search_s"] + c["first_step_s"]
