"""Share of the traced window in which no operation ran on the device."""
from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.idle_percent(ctx["trace"])
