"""Share of the traced window in which the device sat idle while the
engine read its sampled tokens back: idle time under the
``engine.decode.sample`` and ``engine.prefill.sample`` spans
(``spans.Spans.share``)."""


def read(run):
    s = run.spans
    if s is None or s.window_s <= 0:
        return None
    return s.share("readback")
