"""walk_ms.audit: host milliseconds per audit in the
approx rung's MST walk (``approx.walk``: the heapq traversal of the
tree)."""
import spanreduce


def read(run):
    got = spanreduce.summary(run)
    return None if got is None else got["walk_ms"]
