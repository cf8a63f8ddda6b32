"""The engine loop's share of the HBM peak over the traced window.

Least bytes of a whole-graph superstep (``roofline.superstep_least_bytes``
on the graph's own V and E) times the supersteps the window's units ran,
over the window's wall time times the HBM bandwidth of the cell's chips
(each holds its share of the graph), in percent.
"""
from bench import roofline


def read(run):
    if run.trace is None or not run.units:
        return None
    steps = sum(u.supersteps for u in run.units)
    least = steps * roofline.superstep_least_bytes(
        run.num_vertices, run.num_edges, run.queries)
    return 100.0 * roofline.share(least, run.trace.window_s,
                                  run.peaks["hbm_bytes_per_s"], run.chips)
