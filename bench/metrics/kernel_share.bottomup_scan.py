"""``kernels/bottomup.py``'s ``bottomup_scan``: its device seconds in the
trace over the device's busy seconds, in percent."""

KERNEL = "bottomup_scan"


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    seconds = run.trace.kernel_seconds(KERNEL)
    if seconds <= 0:
        return None
    return 100.0 * seconds / run.trace.busy_s
