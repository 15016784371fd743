"""100 x (1 - device-busy time / traced window), where busy is the union
of the device's operation intervals and the window runs from the first
traced job's start to the last one's end."""


def read(w):
    t = w.trace
    if t is None or not t.busy or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
