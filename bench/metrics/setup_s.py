"""Set-up: process start to the first timed step (loading, building the
inputs and weights on the device, compiling or loading from the compile
cache, warm-up)."""


def read(record):
    return record["setup_s"]
