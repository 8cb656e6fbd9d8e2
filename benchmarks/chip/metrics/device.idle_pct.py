"""Share of the traced restore's timed interval in which no operation ran
on the device, averaged over the chips used (%)."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - sum(t.busy_s) / run.chips / t.window_s)
