from repro.imputers.base import (
    ImputationEngine,
    ImputationService,
    Imputer,
    ImputeStore,
)
from repro.imputers.mean import MeanImputer
from repro.imputers.knn import KnnImputer
from repro.imputers.locater import LocaterImputer
from repro.imputers.xgb_hist import XgbHistImputer

__all__ = [
    "ImputationEngine",
    "ImputationService",
    "Imputer",
    "ImputeStore",
    "MeanImputer",
    "KnnImputer",
    "LocaterImputer",
    "XgbHistImputer",
]
