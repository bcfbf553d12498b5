"""Share of ``serve.step`` time spent inside ``serve.admit_chunk``, in %,
from the program's ``obs`` spans, fenced with ``block_until_ready``, over
the first part of a traced window."""


def read(run):
    if not run.spans or not run.spans.get("serve.step"):
        return None
    return 100.0 * sum(run.spans.get("serve.admit_chunk", [])) \
        / sum(run.spans["serve.step"])
