"""Tree estimators over one level-wise binned grower (``grower.py``).

Every family here (forest, decision tree, boosted trees and their
regressors) bins its features once, grows dense-heap trees level by level
inside one XLA program (``grower._grow_fused``) and routes rows between
levels as vector work: a compare-and-select over the level's packed
decision table and the rows of the transposed bin matrix
(``grower._route_rows``), never a per-row gather.  Serving walks the fitted
heaps on raw floats (``grower.forest_leaf_stats``, ``kernels/forest.py``).
"""

from sntc_tpu.models.tree.random_forest import (
    RandomForestClassifier,
    RandomForestClassificationModel,
)
from sntc_tpu.models.tree.gbt import GBTClassifier, GBTClassificationModel
from sntc_tpu.models.tree.gbt_regressor import GBTRegressor, GBTRegressionModel
from sntc_tpu.models.tree.random_forest_regressor import (
    RandomForestRegressor,
    RandomForestRegressionModel,
)
from sntc_tpu.models.tree.decision_tree import (
    DecisionTreeClassifier,
    DecisionTreeClassificationModel,
    DecisionTreeRegressor,
    DecisionTreeRegressionModel,
)

__all__ = [
    "RandomForestClassifier",
    "RandomForestClassificationModel",
    "GBTClassifier",
    "GBTClassificationModel",
    "GBTRegressor",
    "GBTRegressionModel",
    "RandomForestRegressor",
    "RandomForestRegressionModel",
    "DecisionTreeClassifier",
    "DecisionTreeClassificationModel",
    "DecisionTreeRegressor",
    "DecisionTreeRegressionModel",
]
