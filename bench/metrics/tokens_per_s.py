"""tokens_per_s: greedy tokens of every request completed in the window,
over the window (host clock).  The window ends with the last whole batch
that started within ``--seconds``."""


def read(run):
    rec = run.record
    return rec.new_tokens / rec.window_s if rec.window_s > 0 else None
