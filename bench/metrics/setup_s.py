"""Seconds from process start to the first timed request: data, index
build, payload upload, warm-up (compilation or compile-cache loads)."""


def read(run):
    return run.setup_s
