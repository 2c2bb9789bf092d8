"""Output tokens emitted in the window, over the window's length."""
SOURCE = "host_clock"
UNIT = "tokens/s"


def read(w):
    return w.tokens() / w.window_s
