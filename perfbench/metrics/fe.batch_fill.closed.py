"""Requests per dispatched batch over the window: the front end's
``dispatched_requests / batches`` counters."""


def read(run):
    b = run.fe_window.get("batches", 0)
    return run.fe_window["dispatched_requests"] / b if b else None
