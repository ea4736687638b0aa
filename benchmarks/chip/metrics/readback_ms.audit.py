"""readback_ms.audit: host milliseconds per audit spent
waiting for device results and copying them to the host: the sum of the
program's ``device.readback`` spans."""
import spanreduce


def read(run):
    got = spanreduce.summary(run)
    return None if got is None else got["readback_ms"]
