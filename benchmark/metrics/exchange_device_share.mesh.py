"""Device time of the exchange plane (``tensor/exchange.py``), as a
share of the device's busy time in the traced window, in percent.

Both are per-device means over the mesh's devices."""


def read(w):
    from roofline_exchange import device_seconds

    if w.trace is None or w.platform == "cpu":
        return None
    busy = w.trace["busy_s"]
    seconds = device_seconds(w.trace)
    if busy <= 0.0 or seconds <= 0.0:
        return None
    return 100.0 * seconds / busy
