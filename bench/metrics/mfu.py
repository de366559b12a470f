"""``mfu``: the whole round step's share of the chips' bf16 peak.

Counts only the work the algorithm requires, from the FLOPs of one
example that the cell's model family counts (``train_flops`` and
``forward_flops`` of ``bench/families/<family>.py``): forward and
backward passes of the clients that train (K steps of B examples each)
and the forward passes of the evaluations, over the measured window's
host-clock seconds × chips × peak. Local SGD that the program runs for
clients that then estimate, and is discarded, does not count.
"""


def read(run):
    if not run.peaks or run.window_s <= 0 or run.family is None:
        return None
    cfg, tr = run.config, run.config["training"]
    examples = run.trained_client_rounds * tr["local_steps"] * tr["batch_size"]
    flops = (run.family.train_flops(cfg) * examples
             + run.family.forward_flops(cfg) * run.evals
             * cfg["federation"]["test_samples"])
    return 100.0 * flops / (run.window_s * run.chips
                            * run.peaks["flops_bf16"])
