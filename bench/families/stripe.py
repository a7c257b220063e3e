"""Stripe width (arXiv:1302.4760 Fig. 1): a few hot files written once,
then read by every one of ``n_app`` clients."""
from bench import workflows as W


def build(cfg, spec, n_app, cut):
    return W.stripe(n_app, file_bytes=cfg["stripe_file_mb"] * W.MB - cut,
                    n_hot=cfg["stripe_hot_files"],
                    out_bytes=cfg["stripe_out_mb"] * W.MB - cut)
