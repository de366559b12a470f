"""``mla_ms_per_round``: device time of the program's latent attention per
traced round: the union of the intervals of the operations in its
``fed.mla`` scope (forward and backward, in local SGD and in the
evaluation), averaged over the chips the cell uses."""
from bench import trace_scopes

MLA = ("fed.mla",)


def read(run):
    if (not isinstance(run.trace, trace_scopes.ScopedTrace)
            or not run.traced_rounds or not run.devices):
        return None
    s = [trace_scopes.scoped_s(run.trace, d, MLA) for d in run.devices]
    if not any(s):
        return None
    return 1e3 * sum(s) / len(s) / run.traced_rounds
