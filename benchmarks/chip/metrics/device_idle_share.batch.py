"""Share of the traced window with no operation on the device, percent (batch cells)."""

from chipbench.readers import device_idle_pct


def read(run):
    return device_idle_pct(run)
