"""Share of the traced window in which a collective runs on a device, mean
over devices. Source: device trace."""


def read(run):
    if run.trace is None:
        return None
    running, _ = run.trace.collective_seconds()
    return 100.0 * running / run.trace.window_s()
