"""Share of device-busy time in synchronous data movement: copies,
transposes, slices, layout changes and the fusions named for them on the
"XLA Ops" line (async copies that overlap compute are on another line and do
not count). Source: device trace."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.bucket_share("copy")
