"""Bytes the decode steps of the profiled part of the window had to read
(stored weight bytes of the layers and head, gathered embedding rows, and
the live K/V rows of the requests advanced, once per KV head;
``bench/work.py``), over the window's seconds times the chip's HBM
bandwidth, in %."""


def read(run):
    w = run.window
    if not w.decode_steps:
        return None
    return 100.0 * w.decode_bytes / (w.seconds * run.peaks["hbm_bytes_per_s"])
