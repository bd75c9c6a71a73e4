"""Share of the traced window in which no operation ran on the device:
1 − the union of the device's op intervals over the window, averaged
over the devices used (device trace)."""

from . import idle_pct


def read(run):
    return idle_pct(run)
