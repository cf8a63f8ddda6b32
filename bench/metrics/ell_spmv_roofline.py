"""``kernels/ell_spmv.py``'s share of its HBM roofline in the trace.

Per call, the least bytes are one gathered value per stored, unpadded ELL
non-zero and one result per row, per query
(``roofline.ell_spmv_least_bytes``); over the summed device time of the
kernel's trace events times the HBM bandwidth, in percent.  With no dense
block (``k_dense`` 0) the ELL holds every distinct edge and has one row per
vertex; where the planner split off a dense block, or the trace holds no
call, there is nothing this reader can count, and it returns None.  So
too on several chips, where each shard's ELL holds only the edges inside
its shard.
"""
from bench import roofline

KERNEL = "ell_spmv"


def read(run):
    if (run.trace is None or run.chips > 1
            or run.engine.get("k_dense") != 0):
        return None
    calls = run.trace.kernel_calls(KERNEL)
    seconds = run.trace.kernel_seconds(KERNEL)
    if not calls or seconds <= 0:
        return None
    least = calls * roofline.ell_spmv_least_bytes(
        run.distinct_edges, run.num_vertices, run.queries)
    return 100.0 * roofline.share(least, seconds,
                                  run.peaks["hbm_bytes_per_s"])
