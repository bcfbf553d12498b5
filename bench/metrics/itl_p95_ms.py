"""95th percentile of every gap between consecutive tokens of a request,
for each token that came in the window (two tokens made visible by one
step are 0 apart)."""
from stats import percentile


def read(run):
    if not run.window.itl:
        return None
    return percentile(run.window.itl, 95) * 1e3
