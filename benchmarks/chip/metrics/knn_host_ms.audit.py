"""knn_host_ms.audit: host milliseconds per audit in
the approx rung's kNN stage (``approx.knn``: assignment pass, anchor-cell
loop, merge), less its waits for device results (``device.readback``)."""
import spanreduce


def read(run):
    got = spanreduce.summary(run)
    return None if got is None else got["knn_host_ms"]
