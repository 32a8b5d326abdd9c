"""Share of the traced window in which a collective runs on a chip and no
other operation does, averaged over the chips: the halo exchange that
compute did not hide. Nothing to read where no collective ran."""
from bench import trace_reduce


def read(run):
    s = trace_reduce.exposed_collective_s(run.trace)
    return None if s is None else 100.0 * s / run.trace.window_s
