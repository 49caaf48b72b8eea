"""Share of the traced window in which the device sat idle while the
host was inside any other ``engine.*`` span: scheduling, uploads,
dispatch, commit (``spans.Spans.share``)."""


def read(run):
    s = run.spans
    if s is None or s.window_s <= 0:
        return None
    return s.share("host")
