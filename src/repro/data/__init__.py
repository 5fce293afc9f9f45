"""Data pipeline."""
from repro.data.pipeline import DataConfig, SyntheticLMData, make_batch

__all__ = ["DataConfig", "SyntheticLMData", "make_batch"]
