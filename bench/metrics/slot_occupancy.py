"""Requests advanced per decode step, over the engine's slots, in %,
averaged over the decode steps of the profiled part of the window."""


def read(run):
    w = run.window
    if not w.decode_steps:
        return None
    return 100.0 * w.decode_slots / (w.decode_steps * int(run.mix["slots"]))
