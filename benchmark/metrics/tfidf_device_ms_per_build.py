"""Device busy time per TF-IDF build (ms): the union of device-op time in
the traced window over the builds run in it."""


def read(run):
    builds = run.window.counts.get("builds")
    if run.trace is None or not builds or run.trace.n_devices == 0:
        return None
    return run.trace.busy_s / builds * 1e3
