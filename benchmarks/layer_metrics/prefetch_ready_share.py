"""Share of the training thread's gets on ``fit``'s prefetch queue that found
an item waiting (``state="ready"``) rather than blocking for the producer
(``"empty"``), over the window: useful outcomes over attempts. Nothing from a
program without the counter, or where nothing was got. Source: the program's
``dl4jtpu_iterator_gets_total{state}``, delta over the window."""

GETS = "dl4jtpu_iterator_gets_total{state=%s}"


def read(run):
    counts = run.result.get("program", {}).get("fit_iterator", {})
    if GETS % "ready" not in counts:
        return None
    ready, empty = counts[GETS % "ready"], counts.get(GETS % "empty", 0.0)
    return 100.0 * ready / (ready + empty) if ready + empty else None
