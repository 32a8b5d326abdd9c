"""Share of the traced window in which no operation ran on the device,
averaged over the chips used."""
from bench import trace_reduce


def read(run):
    return 100.0 * trace_reduce.idle_share(run.trace)
