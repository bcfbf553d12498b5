"""Seconds the launcher's ``compress`` takes: quantize and Huffman-encode
every weight on the host (host clock around it)."""


def read(run):
    return run.setup["compress_s"]
