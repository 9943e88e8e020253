"""Requests per dispatched batch while the trace ran: the front end's
``dispatched_requests / batches`` counters, read as the trace started
and just before it was stopped, so the stop's stall does not pile one
backlog into one batch."""


def read(run):
    b = run.fe_traced.get("batches", 0)
    return run.fe_traced["dispatched_requests"] / b if b else None
