"""Share of the chips' bf16 peak in the traced window (%): the forward and
backward FLOPs of the client steps whose update reached the merge, plus
the validation forward of every scenario-round, counted from ResNet-18's
shapes, over the window's seconds on the host's clock (the profiler
costs the campaign's traced window no measurable time: 26 calls in
40.65 s, as untraced). Training that is thrown away (clients that did not
join) does not count."""
from bench import shapes


def read(run):
    c, cfg = run.counters, run.config
    model = cfg["model"]
    rounds = c["calls"] * len(run.traffic["p"]) * cfg["max_rounds"]
    flops = (c["delivered_steps"] * cfg["batch_per_client"]
             * shapes.resnet18_train_flops(model)
             + rounds * cfg["val_size"] * 2
             * shapes.resnet18_forward_macs(model))
    peak = run.peaks["bf16_flops"] * run.chips
    return 100.0 * flops / c["elapsed_s"] / peak if c["calls"] else None
