"""Seconds the tuner took to return the cell's plan (host clock around
``autotune`` in set-up)."""


def read(ctx):
    return ctx["run"].counts.get("tune_s")
