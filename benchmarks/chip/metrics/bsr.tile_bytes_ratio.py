"""Bytes of Laplacian tiles the ``bsr`` stepwise kernel streams in one
order, over the bytes of the same Laplacian in the compressed-row form
``opcount.py`` counts (nnz float32 values and int32 column indices, N + 1
int32 row offsets).

Read from the program's layout record (``repro.obs``, always on): the
stored tiles' bytes of the layout prepared last, with the vertex count and
nonzeros of the Laplacian it tiled. A program without the record gives
nothing.
"""

import sys

import opcount


def read(ctx):
    obs = sys.modules.get("repro.obs")
    if obs is None:
        return None
    layout = obs.snapshot().get("layout")
    if not layout or layout.get("backend") != "bsr":
        return None
    csr = layout["nnz"] * (opcount.F32 + opcount.I32) + (layout["n"] + 1) * opcount.I32
    return layout["tile_bytes"] / csr
