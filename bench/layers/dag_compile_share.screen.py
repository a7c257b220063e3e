"""Percent of the window inside the program's compile spans (the DAG
cache and `compile_workflow`)."""
from bench.layers._common import phase_share


def read(ctx):
    return phase_share(ctx, "compile")
