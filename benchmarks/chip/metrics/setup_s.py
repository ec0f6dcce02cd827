"""Process start to window open: weights drawn on the device, engine and
server built, every program loaded (or compiled) and warmed."""


def read(run):
    return run["setup_s"]
