"""Device time of the metrics planes, as a share of the device's busy
time in the traced window, in percent.

The planes are the attribution plane (``tensor/attribution.py``: per-row
traffic counts, sketch and method slots folded every tick) and the
latency ledger (``tensor/ledger.py``).  Their programs run beside the
grain steps on the same device, and the v5e trace names a program by
its jitted function: ``jit_<name>``.
"""

#: the planes' jitted programs, by the module name the trace gives them
MODULES = (
    "jit__plan_kernel",         # attribution: per-batch delta plan
    "jit__apply_checked_stack",  # attribution: planned folds, checked
    "jit__apply_coalesced",     # attribution: coalesced folds
    "jit__snapshot_kernel",     # attribution: snapshot reduction
    "jit__gather_counts",       # attribution: evicted rows' counts
    "jit__zero_rows",           # attribution: evicted rows cleared
    "jit__count_rows_kernel",   # latency ledger: per-batch histogram
)


def read(w):
    if w.trace is None or w.platform == "cpu":
        return None
    busy = w.trace["busy_s"]
    if busy <= 0.0:
        return None
    seconds = sum(w.trace["by_module"].get(m, 0.0) for m in MODULES)
    return 100.0 * seconds / busy
