"""1 minus the union of device-operation intervals over the profiled
window, in %, from the ``jax.profiler`` trace (``bench/devtrace.py``)."""


def read(run):
    if run.device is None:
        return None
    return 100.0 * (1.0 - run.device["busy_s"] / run.device["window_s"])
