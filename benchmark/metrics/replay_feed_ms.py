"""Host time per log spent building the replica and issuing the upload
of its columns (benchmark spans, traced run)."""


def read(run):
    iv = run.trace_interval
    if not iv:
        return None
    build = run.window_spans("replay.build", *iv)
    upload = run.window_spans("replay.upload", *iv)
    if not build:
        return None
    return 1000.0 * (sum(build) + sum(upload)) / len(build)
