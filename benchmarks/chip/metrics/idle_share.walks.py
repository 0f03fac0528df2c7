"""Share of the traced window of walk calls in which no operation ran on
the device, in %: 1 minus the union of device operation intervals over the
window, averaged over the chips."""


def read(reading):
    return reading.trace.idle_share()
