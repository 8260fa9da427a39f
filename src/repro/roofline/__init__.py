from repro.roofline.hlo_cost import CostReport, analyze_hlo
from repro.roofline.analysis import PEAKS, model_flops, peaks_for, roofline_terms

__all__ = ["CostReport", "analyze_hlo", "PEAKS", "peaks_for", "roofline_terms", "model_flops"]
