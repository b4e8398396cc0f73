"""Share of device-busy time spent in Mosaic custom calls
(``custom_call_target="tpu_custom_call"``). Source: device trace."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.bucket_share("pallas")
