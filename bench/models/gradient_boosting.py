"""Gradient boosting: the trees' leaves added (learning rate folded in), a
class where the logit is above 0 (``GradientBoosting.predict``'s strict
``>``)."""
from bench.trees import interval, ops_per_row, raw, read

__all__ = ["interval", "of", "ops_per_row", "raw", "threshold"]

threshold = 0.0


def of(pipeline):
    return read(pipeline, mean=False)
