"""boruvka_host_ms.audit: host milliseconds per
audit in the approx rung's Boruvka stage (``approx.boruvka``: passes, edge
bookkeeping, repair), less its waits for device results
(``device.readback``)."""
import spanreduce


def read(run):
    got = spanreduce.summary(run)
    return None if got is None else got["boruvka_host_ms"]
