"""Device time of the prefill programs and the writes of their K/V into
the slots, found by name (modules whose names start ``jit_prefill(`` and
``jit_slot_write(``), in the traced engine steps, per 1,000 prompt tokens
prefilled in them (``serving.Step.prefill_lens``).  The first token's
read-back is not counted.  A program whose prefill traces under other
names reads nothing."""
from chipbench import trace

PREFIXES = ("jit_prefill(", "jit_slot_write(")


def read(run):
    r = run.reduced
    if r is None or run.window is None:
        return None
    traced = [s for s in run.window.steps if s.traced]
    if len(traced) != len(r.steps):
        raise trace.TraceError(
            f"the harness traced {len(traced)} engine steps, the trace "
            f"holds {len(r.steps)} {trace.HOST_PREFIX}{trace.STEP} "
            f"annotations")
    toks = sum(sum(s.prefill_lens) for s in traced)
    sec = sum(v for d in r.steps for k, v in d.items()
              if k.startswith(PREFIXES))
    return 1e6 * sec / toks if toks and sec > 0 else None
