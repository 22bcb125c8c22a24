"""Share of the traced window in which no operation ran on the device
(%), averaged over the chips. Where the profiler dropped the window's
tail, the window ends at the last recorded device op (``bench/trace.py``)."""


def read(run):
    r = run.reduced
    if r is None:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
