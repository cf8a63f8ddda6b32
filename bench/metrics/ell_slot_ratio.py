"""Slots the hybrid backend's ELL gather reads per real ELL non-zero: the
sums over the program's split builds of the ``repro.ell.pack`` counter's
``slots`` (gathered per query and superstep) over its ``nonzeros``.  1 is
no padding; what is above 1 is the share of the gather spent on sentinel
slots.

Read from the program's counter table after the window; the engine builds
a split once, in set-up, and keeps it.  None where the program has no such
counter or built no split (a backend other than hybrid).
"""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    row = obs.snapshot().get("repro.ell.pack")
    if not row or not row.get("nonzeros"):
        return None
    return row["slots"] / row["nonzeros"]
