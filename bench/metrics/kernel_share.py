"""Share of the device's busy time in the traced window spent in the
program's stencil kernels, averaged over the chips used.

A kernel is an operation whose name starts with the prefix the program
gives every ``pallas_call`` (``stencil_``: ``stencil_temporal``,
``stencil_rowchunk``, ...), whatever number XLA appends to it. The rest
of busy time is the write-backs, the residual, the exchange and copies.
A kernel renamed away from the prefix reads as no kernel time at all.
"""
from bench import trace_reduce

PREFIX = "stencil_"


def read(run):
    tr = run.trace
    busy = trace_reduce.busy_s(tr)
    if busy <= 0:
        return None
    lo, hi = tr.window
    kernel = sum(
        trace_reduce.length(trace_reduce.union(
            ((s, e) for n, s, e in evs
             if trace_reduce.op_name(n).lstrip("%").startswith(PREFIX)),
            lo, hi))
        for evs in tr.devices.values()) / len(tr.devices)
    return 100.0 * kernel / busy
