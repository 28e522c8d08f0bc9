"""Seconds the native strategy search took (`FFModel.search_seconds`)."""


def read(ctx):
    return ctx["counters"]["search_s"]
