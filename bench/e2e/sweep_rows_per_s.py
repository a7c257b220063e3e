"""Op rows simulated per second: each answered candidate's unpadded op
count, by the benchmark's own compiler, over the whole window."""


def read(ctx):
    t0, t1 = ctx["window"]
    refs = ctx["refs"]
    rows = sum(refs.count(wf, lay) for o in ctx["outcomes"] if not o.error
               for wf, lay in zip(o.req.workflows, o.req.layouts))
    return rows / (t1 - t0)
