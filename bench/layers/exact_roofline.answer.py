"""As scan_roofline, for the exact-mode executables and the verified
rows."""
from bench.layers._common import roofline


def read(ctx):
    return roofline(ctx, "exact")
