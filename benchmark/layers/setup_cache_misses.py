"""Persistent-cache requests minus hits during set-up: 0 in every run after
a checkout's first."""


def read(ctx):
    return ctx.compiles_setup["requests"] - ctx.compiles_setup["hits"]
