"""Collectives: device time of the ``all-reduce`` operations (device 0) a
traced step, in ms; nothing where the step holds none (one chip). Every
operation whose name holds ``all-reduce`` is summed. The dp4 ResNet step
compiled for a v5e 2x2 holds 102 of them, all synchronous: the parameter
gradients' and, under GSPMD, the batch norms' statistics over the global
batch, forward and backward. Were one split into ``all-reduce-start`` /
``-done``, the sum would hold what the operation line spends in the two
halves (the exposed part), not the collective's duration."""
from perfbench.trace_reduce import seconds_of


def read(window, trace, config, peaks):
    spent = seconds_of(trace, "all-reduce") if trace else 0
    return 1e3 * spent / trace["steps"] if spent else None
