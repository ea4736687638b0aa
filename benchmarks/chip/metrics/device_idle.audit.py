"""device_idle.audit: share of the traced window in which no operation ran
on the device, averaged over the cell's chips (embedding-audit cells)."""
import tracereduce


def read(run):
    got = run.trace and tracereduce.device_busy(run.trace)
    if not got:
        return None
    busy, window = got
    return 100.0 * (1.0 - busy / window)
