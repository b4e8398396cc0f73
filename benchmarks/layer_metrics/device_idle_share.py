"""Share of the traced window in which no operation ran on the device: 1
minus the union of the "XLA Ops" intervals over the window, mean over the
devices used. Source: device trace."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share()
