"""Mean seconds an admitted question waits in the server before the
dispatcher starts its group (the ``queued|serve-queue`` spans)."""
from bench.layers._serve import queue_wait_mean_s


def read(ctx):
    return queue_wait_mean_s(ctx)
