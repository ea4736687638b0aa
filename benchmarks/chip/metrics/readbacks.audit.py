"""readbacks.audit: blocking device-to-host reads per
audit: the number of the program's ``device.readback`` spans."""
import spanreduce


def read(run):
    got = spanreduce.summary(run)
    return None if got is None else got["readbacks"]
