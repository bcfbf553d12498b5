"""Seconds to make the weights from the seed on the device and bring them
to the host in the program's layout (host clock around it)."""


def read(run):
    return run.setup["init_s"]
