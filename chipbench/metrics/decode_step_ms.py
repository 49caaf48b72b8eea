"""Device time per call of the decode program, from the profiler trace
(``trace.step_work``: the program that ran once in every traced step that
decoded)."""
from chipbench import trace


def read(run):
    if run.reduced is None or run.window is None:
        return None
    w = trace.step_work(run.reduced, [s for s in run.window.steps
                                      if s.traced])
    return 1e3 * w.decode_s / w.decode_calls if w.decode_calls else None
