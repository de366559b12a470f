"""``device_idle_share``: the share of the traced window in which no
operation ran on a chip, averaged over the chips the cell uses."""
from bench import trace_reduce


def read(run):
    if run.trace is None or not run.devices:
        return None
    win = trace_reduce.window_s(run.trace)
    busy = [trace_reduce.busy_s(run.trace, d) for d in run.devices]
    if win <= 0 or not any(busy):
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / win)
