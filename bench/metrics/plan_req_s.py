"""plan_req_s: requests completed by the optimized plan path (fallbacks
and failures excluded) over the window (host clock)."""


def read(run):
    rec = run.record
    return rec.optimized / rec.window_s if rec.window_s > 0 else None
