"""Roofline analysis from compiled dry-run artifacts (§Roofline)."""
from .analysis import (  # noqa: F401
    DRYRUN_TARGET_KIND, PEAKS, analyze_compiled, model_flops, peaks)
from .analysis import parse_collectives  # noqa: F401
