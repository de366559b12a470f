"""``moe_ms_per_round``: device time of the program's expert layers per
traced round: the union of the intervals of the operations in its
``fed.moe`` scope (router, sort, grouped matmuls, combine, shared experts;
in local SGD and in the evaluation), averaged over the chips the cell
uses."""
from bench import trace_scopes

MOE = ("fed.moe",)


def read(run):
    if (not isinstance(run.trace, trace_scopes.ScopedTrace)
            or not run.traced_rounds or not run.devices):
        return None
    s = [trace_scopes.scoped_s(run.trace, d, MOE) for d in run.devices]
    if not any(s):
        return None
    return 1e3 * sum(s) / len(s) / run.traced_rounds
