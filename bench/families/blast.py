"""BLAST (arXiv:1302.4760 §3.2): ``n_app`` app nodes search their share
of the configuration's queries against one shared database."""
from bench import workflows as W


def build(cfg, spec, n_app, cut):
    return W.blast(n_app, n_queries=cfg["n_queries"],
                   db_bytes=cfg["db_mb"] * W.MB - cut,
                   per_query_s=cfg["per_query_s"],
                   query_bytes=cfg["query_mb"] * W.MB - cut,
                   out_bytes=cfg["out_mb"] * W.MB - cut)
