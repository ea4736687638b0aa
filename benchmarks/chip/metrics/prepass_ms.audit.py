"""prepass_ms.audit: host milliseconds per embedding
audit in input admission and the numerics pre-pass: the union of the
program's ``api.admission`` and ``numerics.prepass`` spans
(spanreduce.py)."""
import spanreduce


def read(run):
    got = spanreduce.summary(run)
    return None if got is None else got["prepass_ms"]
