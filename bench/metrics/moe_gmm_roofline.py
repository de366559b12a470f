"""``moe_gmm_roofline``: the expert grouped matmuls' share of their
roofline in local SGD.

Measured: the union of the intervals of the operations in both the
program's ``fed.moe_gmm`` and ``fed.local_sgd`` scopes (the grouped
matmuls of training, not of the evaluation), averaged over the chips.
Roofline: for each traced training step, the larger of the grouped
matmuls' FLOPs over the chip's bf16 peak and their bytes over its HBM
bandwidth, as the cell's model family counts them (``moe_gmm_work``:
the products the program runs, rematerialization included). Traced
steps: traced rounds × the window's trained client-rounds per round × K
(exact where every call trains the same client-rounds, as in
``cc_power``). None where the trace, the family or the peaks give
nothing to read.
"""
from bench import trace_scopes

GMM, LOCAL_SGD = "fed.moe_gmm", "fed.local_sgd"


def _gmm_s(tr, device) -> float:
    def training_gmm(op):
        return (trace_scopes.in_scope(op, GMM)
                and trace_scopes.in_scope(op, LOCAL_SGD))
    return trace_scopes._length(
        trace_scopes._intervals(tr, device, training_gmm)) / 1e9


def read(run):
    if (not isinstance(run.trace, trace_scopes.ScopedTrace)
            or not run.traced_rounds or not run.devices or not run.rounds
            or not run.peaks
            or not hasattr(run.family, "moe_gmm_work")):
        return None
    measured = sum(_gmm_s(run.trace, d) for d in run.devices) / len(
        run.devices)
    if measured <= 0:
        return None
    steps = (run.traced_rounds * run.trained_client_rounds / run.rounds
             * run.config["training"]["local_steps"])
    flops, nbytes = run.family.moe_gmm_work(run.config)
    roof = steps * max(flops / run.peaks["flops_bf16"],
                       nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * roof / measured
