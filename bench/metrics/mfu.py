"""Model operations of every token prefilled and decoded in the profiled
part of the window (``bench/work.py``: no padding, causal attention over
each request's own positions, logits where a token is sampled), over the
window's seconds times the chip's bf16 peak, in %."""


def read(run):
    w = run.window
    if not w.flops:
        return None
    return 100.0 * w.flops / (w.seconds * run.peaks["bf16_flops"])
