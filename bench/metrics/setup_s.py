"""Set-up time: process start to the window's start.  Loading, weights
drawn on the device, warm-up and, in a run that compiles, compilation."""
SOURCE = "host_clock"
UNIT = "s"


def read(w):
    return w.setup_s
