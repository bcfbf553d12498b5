"""Seconds the launcher's ``load`` takes: decode the container into
resident weights, ended by ``block_until_ready`` (host clock around it)."""


def read(run):
    return run.setup["load_s"]
