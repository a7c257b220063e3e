"""Broadcast (arXiv:1302.4760 Fig. 6): one producer, ``n_app``
consumers; the spec's ``replication`` copies the hot file eagerly."""
from bench import workflows as W


def build(cfg, spec, n_app, cut):
    return W.broadcast(n_app,
                       file_bytes=cfg["broadcast_file_mb"] * W.MB - cut,
                       out_bytes=cfg["broadcast_out_mb"] * W.MB - cut,
                       replication=spec["replication"])
