"""Share of the traced window in which no op ran on the device (%),
averaged over the chips used."""


def read(run):
    t = run.trace
    if t is None or t.n_devices == 0 or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
