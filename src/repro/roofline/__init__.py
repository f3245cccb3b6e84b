from repro.roofline.hw import CHIPS, TPU_V5E, chip_for
from repro.roofline.analysis import analyze_compiled, roofline_terms

__all__ = ["CHIPS", "TPU_V5E", "chip_for", "analyze_compiled",
           "roofline_terms"]
