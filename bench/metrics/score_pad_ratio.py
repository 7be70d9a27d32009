"""Neighbour slots the score-kernel launches send, once padded to the
engine's widths and the kernel's tiling, over the slots the chunks or
supersteps really have (program counters ``score_slots_padded`` and
``score_slots_true``, summed over the window's jobs). None where the program
counts neither, or no launch ran."""


def read(run):
    tels = [r["telemetry"] for r in run.records]
    padded = sum(t.get("score_slots_padded", 0) for t in tels)
    true = sum(t.get("score_slots_true", 0) for t in tels)
    if not padded or not true:
        return None
    return padded / true
