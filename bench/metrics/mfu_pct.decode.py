"""Required FLOPs of the tokens decoded in the window over the decode-block programs' device time at peak (%)."""

from bench import roofline


def read(run):
    return roofline.decode_mfu(run)
