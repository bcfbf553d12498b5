"""Output tokens that became visible in the window, over its seconds."""


def read(run):
    return run.window.tokens / run.window.seconds
