"""Share of the window in which the chips ran no operation, in percent, in
the partition cells (``bench.trace.read_device_idle``)."""
from bench.trace import read_device_idle as read  # noqa: F401
