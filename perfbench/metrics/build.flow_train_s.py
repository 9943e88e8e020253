"""Seconds the bulk load spent training the flow (the program's own
``nfl.metrics["flow_train_s"]``)."""


def read(run):
    v = run.build.get("flow_train_s")
    return None if v is None else float(v)
