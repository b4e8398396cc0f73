"""The late-generator number: share of the window the closed-loop clients
spent between a result arriving and the next step being sent (argmax, one-hot
and waiting for the interpreter), so that a starved client is not read as a
slow server. Source: host clock, client side."""


def read(run):
    share = run.result.get("client_late_share")
    return None if share is None else 100.0 * share
