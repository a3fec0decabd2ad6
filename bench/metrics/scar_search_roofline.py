"""``scar_search`` kernel time against the HBM bytes the screen needs.

Kernel time: the device durations of the screening kernel's calls in the
traced window (``work.kernel_calls``: custom calls returning
s32[beam, N]).  Each call is placed in its window by the device program
execution (``jit_fused_program``) that holds it.  Bytes per window: one
screen per model of the candidates the stage always screens
(``work.pool_screened``), and for each further call of that window (a
stage that fell back to its whole batch) the whole batch of the window's
smallest model, a lower bound.  Share = bytes / peak HBM bandwidth /
kernel time, in percent.
"""
import work


def read(run):
    if run.trace is None or not run.peaks:
        return None
    beam, keep = run.search.beam, run.search.keep_per_model
    calls = work.kernel_calls(run.trace.op_events, "search", beam)
    progs = [m for m in run.trace.modules
             if m[0].startswith(work.FUSED_MODULE)]
    windows = [window for replan in run.work() for window in replan]
    if not calls or len(progs) != len(windows):
        return None
    nbytes = 0.0
    for (_name, start, dur), window in zip(progs, windows):
        n = sum(1 for s, _d in calls if start <= s < start + dur)
        words = window[0].words32
        nbytes += sum(work.search_bytes(work.pool_screened(mw, keep), beam,
                                        words) for mw in window)
        extra = n - len(window)
        if extra > 0:
            nbytes += extra * min(work.search_bytes(mw.n_cands, beam, words)
                                  for mw in window)
    t_s = sum(d for _s, d in calls) * 1e-9
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / t_s
