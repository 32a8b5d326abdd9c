"""Mean sweeps a tolerance-driven solve ran, as the program reports them
(``iters_done``)."""


def read(run):
    sweeps = run.work["sweeps"]
    return sum(sweeps) / len(sweeps) if sweeps else None
