from .analysis import (
    PEAKS, V5E, ChipPeaks, CollectiveStats, collective_summary, model_flops,
    parse_collectives, peaks_for, roofline_terms, summarize_cell,
)

__all__ = [
    "PEAKS", "V5E", "ChipPeaks", "CollectiveStats", "collective_summary",
    "model_flops", "parse_collectives", "peaks_for", "roofline_terms",
    "summarize_cell",
]
