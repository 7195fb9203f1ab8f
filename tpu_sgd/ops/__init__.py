from tpu_sgd.ops.gradients import (
    Gradient,
    HingeGradient,
    LeastSquaresGradient,
    LogisticGradient,
    MultinomialLogisticGradient,
)
from tpu_sgd.ops.gram import GramData, GramLeastSquaresGradient
from tpu_sgd.ops.pallas_kernels import fused_gradient_sums
from tpu_sgd.ops.sparse import (
    append_bias_auto,
    append_bias_bcoo,
    csr_to_bcoo,
    is_sparse,
    load_libsvm_file_bcoo,
    row_matrix_bcoo,
    sparse_data,
    take_rows_bcoo,
)
from tpu_sgd.ops.updaters import (
    L1Updater,
    SimpleUpdater,
    SquaredL2Updater,
    Updater,
)

__all__ = [
    "Gradient",
    "LeastSquaresGradient",
    "LogisticGradient",
    "HingeGradient",
    "MultinomialLogisticGradient",
    "GramData",
    "GramLeastSquaresGradient",
    "fused_gradient_sums",
    "is_sparse",
    "csr_to_bcoo",
    "load_libsvm_file_bcoo",
    "append_bias_bcoo",
    "append_bias_auto",
    "row_matrix_bcoo",
    "take_rows_bcoo",
    "sparse_data",
    "Updater",
    "SimpleUpdater",
    "L1Updater",
    "SquaredL2Updater",
]
