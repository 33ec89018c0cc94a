"""Share of its roofline reached by the generated LSTM stage kernel (Mosaic custom call) over the window's prefills (%)."""

from bench import roofline


def read(run):
    return roofline.stage_kernel(run)
