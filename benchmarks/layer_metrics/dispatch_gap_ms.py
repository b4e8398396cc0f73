"""Mean device-idle time at a dispatch boundary: between the middles of two
consecutive ``dispatch`` spans (the harness's TraceAnnotation around each
``fit_on_device`` call) there is one boundary, and what the device idles
there is its gap. Source: device trace + harness spans."""


def read(run):
    if run.trace is None:
        return None
    gaps = run.trace.gaps_between("dispatch")
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
