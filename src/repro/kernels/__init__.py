"""Pallas kernels for the paper's compute hot-spots, behind one registry.

Six kernel packages (gemm, stream, spmv, jacobi2d, qc_gate, flash_decode),
each validated against a pure-jnp/numpy oracle in ``<pkg>/ref.py``.  The
jit call surfaces live in :mod:`repro.kernels.registry`: every kernel is a
``KernelOps`` exposing ``ref`` / ``kernel`` / ``interpret`` variants and is
auto-registered as a ``Workload`` (``kernel/<name>``) for
``repro.analysis.analyze``.

    from repro.kernels import registry

    y = registry.GEMM(x, w)                  # compiled; interpret mode on CPU
    y = registry.GEMM.kernel(x, w)           # compiled Pallas path
    y_ref = registry.GEMM.ref(x, w)          # oracle
    registry.list_kernels()                  # all nine entry points

The per-package ``ops.py`` modules remain as thin shims re-exporting the
registry objects plus their package-specific cost/issue models.
"""

from repro.kernels import registry  # noqa: F401
from repro.kernels.registry import (  # noqa: F401
    KERNELS,
    KernelOps,
    get_kernel,
    list_kernels,
    register_kernel,
)
