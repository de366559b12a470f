"""``mfu``: the whole round step's share of the chips' bf16 peak.

Counts only the work the algorithm requires, from the benchmark's own
FLOP counter: forward and backward passes of the clients that train
(K steps of B images each) and the forward passes of the evaluations,
over the measured window's host-clock seconds × chips × peak. Local SGD
that the program runs for clients that then estimate, and is discarded,
does not count.
"""
from bench.flop_count import forward_flops, train_flops


def read(run):
    if not run.peaks or run.window_s <= 0:
        return None
    model, tr = run.config["model"], run.config["training"]
    images = run.trained_client_rounds * tr["local_steps"] * tr["batch_size"]
    flops = (train_flops(model) * images + forward_flops(model)
             * run.evals * run.config["federation"]["test_samples"])
    return 100.0 * flops / (run.window_s * run.chips
                            * run.peaks["flops_bf16"])
