"""Share of the traced window in which a collective runs on a device and no
other operation does, mean over devices. Source: device trace."""


def read(run):
    if run.trace is None:
        return None
    _, exposed = run.trace.collective_seconds()
    return 100.0 * exposed / run.trace.window_s()
