"""Required prefill FLOPs of the prompts admitted in the window over the prefill programs' device time at peak (%)."""

from bench import roofline


def read(run):
    return roofline.prefill_mfu(run)
