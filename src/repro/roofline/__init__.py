from repro.roofline.analysis import ChipPeaks, PEAKS, Roofline, peaks_for
from repro.roofline.hlo_parse import HloAnalysis, analyze_hlo
