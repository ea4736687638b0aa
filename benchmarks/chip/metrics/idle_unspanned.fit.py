"""idle_unspanned.fit: the share of the device's
idle time inside the exact assessments that no non-root program span
covers, so that no host stage of the program names it (spanreduce.py)."""
import spanreduce


def read(run):
    got = spanreduce.summary(run)
    return None if got is None else got.get("idle_unspanned")
