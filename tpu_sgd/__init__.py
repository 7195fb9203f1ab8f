"""tpu_sgd: a TPU-native framework with the capabilities of
``Patrickgsheng/spark-parallelized-sgd`` (Spark-MLlib-style parallelized
mini-batch SGD for generalized linear models).

The reference's capability contract is preserved — the
Optimizer × Gradient × Updater plugin boundary, the model families
(Linear/Lasso/Ridge regression, logistic regression, linear SVM, streaming
variants), seeded mini-batch sampling, loss history, convergence tolerance,
and sparse (BCOO) feature training that never densifies — re-designed
TPU-first: fused XLA matvec gradient steps, a whole-run ``lax.while_loop``
driver, and ``shard_map`` + ``lax.psum`` data parallelism over ICI for
dense rows and equal-nse sparse blocks alike.  See SURVEY.md for the
reference analysis this build follows.
"""

from tpu_sgd.config import MeshConfig, SGDConfig
from tpu_sgd.evaluation import (BinaryClassificationMetrics,
                                MulticlassMetrics, RegressionMetrics)
from tpu_sgd.feature import Normalizer, StandardScaler, StandardScalerModel
from tpu_sgd.linalg import BLAS, DenseVector, SparseVector, Vectors
from tpu_sgd.models import *  # noqa: F401,F403
from tpu_sgd.models import __all__ as _models_all
from tpu_sgd.ops import *  # noqa: F401,F403
from tpu_sgd.ops import __all__ as _ops_all
from tpu_sgd.optimize import (GradientDescent, LBFGS, NormalEquations,
                              OWLQN, Optimizer, row_capacity, run_lbfgs,
                              run_mini_batch_sgd)
from tpu_sgd.parallel import data_mesh, make_mesh
# NOTE: the bare `plan` FUNCTION is deliberately not re-exported here —
# `from tpu_sgd.plan import x` would still work, but the package attribute
# `tpu_sgd.plan` must keep naming the MODULE (an `import tpu_sgd.plan as m`
# resolves the package attribute and would get the function instead).
from tpu_sgd.plan import (CostModel, Plan, device_budget, plan_for,
                          plan_quasi_newton)
from tpu_sgd.stat import MultivariateStatisticalSummary, col_stats, corr
# serving subsystem (imported last: it builds on models + utils above)
from tpu_sgd.serve import (BackpressureError, ModelRegistry, PredictEngine,
                           Server)

__version__ = "0.1.0"

__all__ = (
    ["SGDConfig", "MeshConfig", "Vectors", "DenseVector", "SparseVector", "BLAS"]
    + list(_models_all)
    + list(_ops_all)
    + ["GradientDescent", "LBFGS", "NormalEquations", "OWLQN", "Optimizer",
       "run_mini_batch_sgd", "run_lbfgs", "row_capacity",
       "data_mesh", "make_mesh",
       "CostModel", "Plan", "device_budget", "plan_for",
       "plan_quasi_newton",
       "Normalizer", "StandardScaler", "StandardScalerModel",
       "RegressionMetrics", "BinaryClassificationMetrics",
       "MulticlassMetrics",
       "col_stats", "corr", "MultivariateStatisticalSummary",
       "Server", "ModelRegistry", "PredictEngine", "BackpressureError"]
)
