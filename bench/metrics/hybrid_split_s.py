"""Host seconds of the hybrid backend's degree split: the self time of the
program's ``repro.hybrid.split`` span (``BSPEngine._build_hybrid``: the
degree split and the push arrays).

Read from the program's span table after the window.  The engine builds a
split once, while the warm-up unit traces its loop, and keeps it, so the
whole of the time is set-up.  None where the program has no span table or
built no split (a backend other than hybrid).
"""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    row = obs.snapshot().get("repro.hybrid.split")
    return None if row is None else row["self_s"]
