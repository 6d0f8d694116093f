"""Set-up: process start to the start of the window (device init, trace
synthesis, compile or cache load, warm-up)."""


def read(record):
    return record.setup_s
