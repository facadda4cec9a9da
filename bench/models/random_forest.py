"""Random forest: the trees' leaves averaged, a class where the mean leaf is
above 0.5 (``RandomForest.predict``'s strict ``>``)."""
from bench.trees import interval, ops_per_row, raw, read

__all__ = ["interval", "of", "ops_per_row", "raw", "threshold"]

threshold = 0.5


def of(pipeline):
    return read(pipeline, mean=True)
