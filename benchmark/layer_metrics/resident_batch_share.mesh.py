"""Batches of the mesh's sharded scans that a step was handed from the page cache's
entry, over those and the batches generated for it, in percent
(``mesh_scan_batches_resident`` and ``mesh_scan_batches_generated``, window deltas): 100
where every scan of the window is resident as quarters, 0 where each passes the entry
cap a chip and streams, 50 where a round has as many batches of either.  None on a
program without the counters or in a window that scanned nothing on the mesh."""


def read(ctx):
    resident = ctx.counters.get("mesh_scan_batches_resident")
    generated = ctx.counters.get("mesh_scan_batches_generated")
    if resident is None or generated is None or not resident + generated:
        return None
    return 100.0 * resident / (resident + generated)
