"""Roofline accounting from compiled dry-run artifacts."""

from .analysis import (RooflineReport, analyze_compiled, collective_bytes,
                       roofline_terms)
from .hw import HW_V5E, HWSpec

__all__ = ["RooflineReport", "analyze_compiled", "collective_bytes",
           "roofline_terms", "HW_V5E", "HWSpec"]
