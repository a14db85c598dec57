"""Seconds from the start of the process to the opening of the window:
imports, weights, plans, compilation or cache loads, warm-up."""


def read(ctx):
    return ctx["setup_s"]
