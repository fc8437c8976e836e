"""Share of the traced window in which no operation ran on the device, in
long-document cells."""

from _serving import device_idle


def read(run):
    return device_idle(run)
