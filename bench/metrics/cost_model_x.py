"""cost_model_x: how far the solver's predicted latency of the plan
(``ExecutionPlan.latency_s``) lies from the device time per request in
the profiler trace (busy time of the window over the requests completed
in it): max(m / p, p / m), >= 1."""


def read(run):
    t, rec = run.trace, run.record
    p = getattr(run.entry, "plan_latency_s", None)
    if t is None or not rec.completed or not p or t.busy_s <= 0:
        return None
    m = t.busy_s / rec.completed
    return max(m / p, p / m)
