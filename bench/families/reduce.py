"""Reduce (arXiv:1302.4760 Fig. 5): ``n_app`` producers and one
consumer; the spec's ``wass`` collocates the intermediate files."""
from bench import workflows as W


def build(cfg, spec, n_app, cut):
    return W.reduce_(n_app, in_bytes=cfg["reduce_in_mb"] * W.MB - cut,
                     mid_bytes=cfg["reduce_mid_mb"] * W.MB - cut,
                     out_bytes=cfg["reduce_out_mb"] * W.MB - cut,
                     wass=spec["wass"])
