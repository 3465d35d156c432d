"""Seconds from the process start to the window's opening: imports, device
start, weights made on the device, engine built, the step's two programs
compiled or loaded from the compile cache, and the traffic run to steady
state."""


def read(run):
    return run.setup_s
