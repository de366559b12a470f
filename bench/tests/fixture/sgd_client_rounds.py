"""``sgd_client_rounds``: the program's ``local_sgd_client_rounds`` counter
across the measured window (``RunRecord.counters``), for the test that a
metric reads the program's counters with files alone."""

COUNTER = "local_sgd_client_rounds"


def read(run):
    return run.counters.get(COUNTER)
