"""Montage (Juve et al., FGCS 29(3), 2013): the 2MASS mosaic workflow's
nine transformations on a regular overlap model.

Images lie on a ``grid_side`` x ``grid_side`` grid. Each is projected
(mProjectPP); each pair of right, down and down-right neighbours is
fitted (mDiffFit); the fits are concatenated (mConcatFit) into one
background model (mBgModel), which corrects every projected image
(mBackground). Square tiles, ``tiles_per_side`` to a side, each list
their corrected images (mImgtbl), co-add them (mAdd) and shrink the
result (mShrink); the shrunk tiles are listed, co-added and rendered
once more (mImgtbl, mAdd, mJPEG). The spec's ``wass`` collocates the
fits as one group and each tile's corrected images as a group of their
own (arXiv:1301.6195). Tasks go to the compiler's scheduler, so
``n_app`` does not change the workflow.

This is the benchmark's own copy of the workflow: it imports nothing of
the system under test.
"""
from bench import workflows as W


def build(cfg, spec, n_app, cut):
    k, t = cfg["grid_side"], cfg["tiles_per_side"]
    mb, run = cfg["file_mb"], cfg["runtime_s"]

    def size(what, scale=1):
        return int(mb[what] * scale * W.MB) - cut

    def collocate(name, group):
        return {name: {"placement": "collocate", "group": group}} \
            if spec["wass"] else None

    tasks = []

    def task(kind, inputs, outputs, attrs=None):
        tasks.append(W._task(len(tasks), inputs, outputs,
                             runtime=run[kind], stage=kind, attrs=attrs))

    cells = [(r, c) for r in range(k) for c in range(k)]
    img = {rc: f"{rc[0]}_{rc[1]}" for rc in cells}
    pre = [[f"raw{img[rc]}", size("raw"), None] for rc in cells]
    for rc in cells:
        task("mProjectPP", [f"raw{img[rc]}"],
             [[f"proj{img[rc]}", size("projected")]])
    fits = []
    for r, c in cells:
        for dr, dc in ((0, 1), (1, 0), (1, 1)):
            if r + dr < k and c + dc < k:
                a, b = img[(r, c)], img[(r + dr, c + dc)]
                fit = f"fit{a}-{b}"
                task("mDiffFit", [f"proj{a}", f"proj{b}"],
                     [[fit, size("fit")]], collocate(fit, "fits"))
                fits.append(fit)
    task("mConcatFit", fits, [["fits.tbl", size("fits_tbl")]])
    task("mBgModel", ["fits.tbl"], [["corrections", size("corrections")]])
    tiles = {}
    for rc in cells:
        tiles.setdefault((rc[0] * t // k, rc[1] * t // k), []).append(rc)
    tile_of = {rc: tid for tid, rcs in tiles.items() for rc in rcs}
    for rc in cells:
        out = f"corr{img[rc]}"
        task("mBackground", [f"proj{img[rc]}", "corrections"],
             [[out, size("corrected")]],
             collocate(out, "tile%d_%d" % tile_of[rc]))
    names = {tid: "%d_%d" % tid for tid in tiles}
    for tid, rcs in tiles.items():
        task("mImgtbl", [f"corr{img[rc]}" for rc in rcs],
             [[f"tile{names[tid]}.tbl", size("table")]])
    for tid, rcs in tiles.items():
        task("mAdd", [f"tile{names[tid]}.tbl"]
             + [f"corr{img[rc]}" for rc in rcs],
             [[f"mosaic{names[tid]}", size("mosaic_per_image", len(rcs))]])
    shrunk = []
    for tid, rcs in tiles.items():
        out = f"shrunk{names[tid]}"
        task("mShrink", [f"mosaic{names[tid]}"],
             [[out, size("mosaic_per_image",
                         len(rcs) / cfg["shrink_factor"])]])
        shrunk.append(out)
    task("mImgtbl", shrunk, [["mosaic.tbl", size("table")]])
    task("mAdd", ["mosaic.tbl"] + shrunk,
         [["mosaic", size("mosaic_per_image", k * k / cfg["shrink_factor"])]])
    task("mJPEG", ["mosaic"], [["mosaic.jpg", size("jpeg")]])
    return {"name": f"montage_{k}x{k}_{'wass' if spec['wass'] else 'dss'}",
            "tasks": tasks, "preloaded": pre}
