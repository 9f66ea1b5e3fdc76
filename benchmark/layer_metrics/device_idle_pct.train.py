"""1 - union of the device's operation intervals over the traced window."""
from benchmark import xplane


def read(run):
    return xplane.idle_pct(run["trace"])
