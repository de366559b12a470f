"""``eval_share``: the share of the measured window that the host spent
in the evaluations (the benchmark's ``eval`` host spans)."""


def read(run):
    if run.window_s <= 0:
        return None
    return 100.0 * sum(s for n, s in run.spans if n == "eval") / run.window_s
