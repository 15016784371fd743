"""Seconds from process start to the window's start: imports, device
initialisation, plan(), the stream, compile or cache load, and the
warm-up job."""


def read(w):
    return w.setup_s
