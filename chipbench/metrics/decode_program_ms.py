"""Device time per execution of the decode program, found by its name:
the module whose name starts ``jit_decode_step(``, over the traced slice
(``trace.Reduced.modules``).  A program whose decode step traces under
another name reads nothing."""

PREFIX = "jit_decode_step("


def read(run):
    r = run.reduced
    if r is None:
        return None
    hits = [v for k, v in r.modules.items() if k.startswith(PREFIX)]
    calls = sum(c for c, _ in hits)
    return 1e3 * sum(s for _, s in hits) / calls if calls else None
