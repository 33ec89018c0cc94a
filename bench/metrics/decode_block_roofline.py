"""Share of its roofline reached by the decode-block program (K-step scan over lm.decode_step) (%)."""

from bench import roofline


def read(run):
    return roofline.decode_block(run)
