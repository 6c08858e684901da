"""setup_s: process start until the first request is due (host clock):
key-set generation, index build, placement, warm-up and compiles."""


def read(run):
    return run.setup_s
