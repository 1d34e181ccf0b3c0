"""The device's idle share of the traced window, in percent: one minus
the union of its operations' intervals over the window."""


def read(trace, facts, peaks):
    if trace.window_s <= 0 or trace.busy_s() <= 0:
        return None
    return 100.0 * trace.idle_share()
