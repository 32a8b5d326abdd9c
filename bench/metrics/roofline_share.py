"""Share of the kernel roofline: the least time the window's useful work
could take on the chips used (``bench/workcount.py``, against the peaks of
``bench/peaks.json``), over the device's busy time in the window.

Useful work counts the sweeps each solve was asked for or reported
(``iters_done``), never sweeps run on frozen lanes, halo recompute or the
residual checks, so work that does not advance a solve lowers the share.
The busy time is every device operation in the window, whatever it is
called, so a kernel that is renamed or replaced is still counted.
"""
from bench import trace_reduce, workcount


def read(run):
    busy = trace_reduce.busy_s(run.trace)
    if not run.work["ops"] or busy <= 0:
        return None
    t, _ = workcount.least_time_s(
        run.work["ops"], run.work["bytes"],
        vector_ops_per_s=run.peaks["vector_f32_ops_per_s"],
        hbm_bytes_per_s=run.peaks["hbm_bytes_per_s"], chips=run.chips)
    return 100.0 * t / busy
