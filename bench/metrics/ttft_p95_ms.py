"""95th percentile of time to first token, over every request whose first
token came in the window: from the moment its client sent it to the moment
the token was on the host."""
from stats import percentile


def read(run):
    if not run.window.ttft:
        return None
    return percentile(run.window.ttft, 95) * 1e3
