"""The exchange of Presence game updates between chips against its
roofline: one chip's share of the least time the crossing updates need
at the chip's HBM and interconnect peaks, over the exchange's device
time (``roofline_exchange``)."""


def read(w):
    import roofline_exchange as rx
    import spec

    if w.trace is None or w.platform == "cpu":
        return None
    seconds = rx.device_seconds(w.trace)
    if seconds <= 0.0:
        return None
    peaks = dict(spec.peaks(w.device_kind, w.cell.root),
                 **rx.ici_peaks(w.device_kind, w.cell.root))
    return 100.0 * rx.least_seconds(w.work, w.cell.chips, peaks) / seconds
