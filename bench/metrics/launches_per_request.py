"""Launches the solve server made per request it completed in the window
(``SolveServer.stats()``, less the launches of its warm-up)."""


def read(run):
    done = run.counters.get("completed")
    return run.counters["launches"] / done if done else None
