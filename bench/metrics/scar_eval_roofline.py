"""``scar_eval`` kernel time against the HBM bytes the scoring needs.

Kernel time: the device durations of the scoring kernel's calls in the
traced window (``work.kernel_calls``: custom calls returning f32[2, B]),
each placed in its window by the device program execution
(``jit_fused_program``) that holds it.  Bytes: ``work.eval_bytes`` of
every model's real candidate batch in those windows.  Share = bytes /
peak HBM bandwidth / kernel time, in percent.
"""
import work


def read(run):
    if run.trace is None or not run.peaks:
        return None
    calls = work.kernel_calls(run.trace.op_events, "eval")
    progs = [m for m in run.trace.modules
             if m[0].startswith(work.FUSED_MODULE)]
    windows = [window for replan in run.work() for window in replan]
    if not calls or len(progs) != len(windows):
        return None
    t_ns = nbytes = 0.0
    for (_name, start, dur), window in zip(progs, windows):
        inside = [d for s, d in calls if start <= s < start + dur]
        if inside:
            t_ns += sum(inside)
            nbytes += sum(work.eval_bytes(mw) for mw in window)
    if t_ns <= 0:
        return None
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / (t_ns * 1e-9)
