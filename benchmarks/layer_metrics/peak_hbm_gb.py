"""What the fullest chip held at the window's edges, in GB (1e9): live
arrays plus what the runtime reserves for the loaded programs, read together
(``harness/main.py run_cell``, ``harness/gate.py memory_held_bytes``).
Source: the runtime's counters."""


def read(run):
    return run.memory_peak_bytes / 1e9
