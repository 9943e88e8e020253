"""Host milliseconds an insert call holds the loop: the union time of
the benchmark's ``index.insert`` span over the insert calls made while
the trace ran (the delta append, the tier upload and any fold tick)."""


def read(run):
    t = run.trace
    n = run.traced.get("insert_calls", 0)
    if t is None or not n:
        return None
    s = t.span_seconds("index.insert")
    return s * 1e3 / n if s > 0 else None
