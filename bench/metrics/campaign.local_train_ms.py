"""Device time of the ops under the ``campaign/local_train`` scope, per
scenario-round (ms)."""
from bench.trace import seconds_under


def read(run):
    c = run.counters
    rounds = c["calls"] * len(run.traffic["p"]) * run.config["max_rounds"]
    s = seconds_under(run.reduced, "campaign/local_train")
    return s / rounds * 1e3 if rounds and s > 0 else None
