"""Share of the traced window in which no operation ran on the device (%)."""

from bench import roofline


def read(run):
    return roofline.idle(run)
