"""``conv_ms_per_round``: device time of the convolutions that the span
program runs, per round, on the cell's first chip. Local SGD and its
gradients are almost all convolutions, so this is the local-SGD layer's
time."""
from bench import trace_reduce

SPAN_PROGRAM = "run_span"


def is_span_conv(op) -> bool:
    return SPAN_PROGRAM in op.program and "convolution" in op.text()


def read(run):
    if run.trace is None or not run.traced_rounds:
        return None
    s = trace_reduce.op_seconds(run.trace, run.devices[0], is_span_conv)
    return 1e3 * s / run.traced_rounds if s > 0 else None
