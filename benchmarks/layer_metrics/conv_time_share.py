"""Share of device-busy time in convolutions, dots and the output fusions
rooted in one (``kind=kOutput``): the time the MXU can be doing the model's
FLOPs. Source: device trace."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.bucket_share("mxu")
