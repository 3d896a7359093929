"""Roofline analysis from compiled dry-run artifacts (no real hardware)."""
from repro.roofline.analysis import (
    HW, PEAKS, HloAnalysis, analyze_hlo_text, hw_for, roofline_terms,
    model_flops,
)

__all__ = ["HW", "PEAKS", "HloAnalysis", "analyze_hlo_text", "hw_for",
           "roofline_terms", "model_flops"]
