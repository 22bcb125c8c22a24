"""Share of the vmapped while-loop's lane-iterations that a lane needed
(%): mean over a batch's fleets of the sweeps each ran, over the sweeps
the slowest ran, averaged over the batches of the window. Read from the
solver's own ``iters`` output."""


def read(run):
    u = run.counters["lane_useful"]
    return 100.0 * sum(u) / len(u) if u else None
