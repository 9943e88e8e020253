"""Seconds the bulk load spent building the flat index (the program's
own ``nfl.metrics["index_build_s"]``)."""


def read(run):
    v = run.build.get("index_build_s")
    return None if v is None else float(v)
