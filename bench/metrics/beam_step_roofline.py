"""``beam_step``'s share of its roofline (%)."""
from bench.readers import roofline_share


def read(run):
    return roofline_share(run, "beam_step")
