"""Per-layer-kind K-FAC math: captures -> factors, grads <-> matrices."""

from distributed_kfac_pytorch_tpu.layers.base import (
    GRAD_QUADRATIC_KEYS,
    KNOWN_KINDS,
    compute_a_factor,
    compute_g_factor,
    compute_tied_factor_extras,
    experts_contrib,
    factor_shapes,
    grads_to_matrix,
    matrix_to_grads,
)
