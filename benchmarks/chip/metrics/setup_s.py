"""Set-up time: process start until the window opens (TPU runtime start,
weights from the seed, pack, compile or cache load, warm-up, payloads)."""


def read(run):
    return run.setup_s
