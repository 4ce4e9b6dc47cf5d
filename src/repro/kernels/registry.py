"""Kernel registry: one jit-wrapper factory for every Pallas kernel.

This is the paper's Sec. 3.2 micro-benchmark suite (GEMM, STREAM, SpMV,
Jacobi2D, the QC RX gate, flash-decode) behind one registration surface.
Each ``kernels/<pkg>/ops.py`` used to hand-roll the same
``functools.partial(jax.jit, static_argnames=(..., "interpret"))`` wrapper.
:func:`register_kernel` replaces those six copies with one factory that
returns a :class:`KernelOps` exposing the call surfaces:

* ``op(*args)``        — default call: the compiled kernel, or interpret
  mode on the CPU backend, where it is the only option;
* ``op.kernel(*args)`` — compiled Pallas path (``interpret=False``);
* ``op.interpret(*args)`` — explicit interpret-mode path;
* ``op.ref(*args)``    — the pure-jnp/numpy oracle.

Registration also auto-registers the kernel as a :class:`~repro.analysis.
workload.Workload` (name ``kernel/<name>``) with a small example problem
and the ref module's analytic flops/bytes model (paper Sec. 3.3), so every
kernel is reachable through ``repro.analysis.analyze`` with zero extra
wiring — and, when a :class:`~repro.tuning.space.TuningSpace` is attached,
through the roofline-guided autotuner (``repro.tuning``): after a
``tune()`` the ops object resolves its best-known block config at call
time, with explicit keyword arguments always winning.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from repro.analysis.workload import Workload, register_lazy
from repro.tuning import spaces as _spaces
from repro.tuning.space import TuningSpace, canonical_dtype


def default_interpret() -> bool:
    """Whether a call that does not choose runs the Pallas interpreter.

    Only the CPU backend defaults to it, because it cannot run a compiled
    TPU kernel; on a TPU backend the default is the compiled kernel, so a
    run on the chip never measures the interpreter by accident.
    """
    return jax.default_backend() == "cpu"


class KernelOps:
    """Call surface for one registered kernel (ref / kernel / interpret).

    When a :class:`TuningSpace` is attached and a tuned config is active
    (installed by ``repro.tuning.tune``/``load_tuned``), calls resolve the
    tuned static arguments automatically: the config is validated against
    the actual call arguments (clamp + divisibility) and merged only for
    keywords the caller did not pass — explicit kwargs always win.
    """

    def __init__(
        self,
        name: str,
        kernel_fn: Callable,
        ref_fn: Optional[Callable] = None,
        *,
        static_argnums: Tuple[int, ...] = (),
        static_argnames: Tuple[str, ...] = (),
        tuning_space: Optional[TuningSpace] = None,
    ) -> None:
        self.name = name
        self.raw = kernel_fn
        self._ref = ref_fn
        self.tuning_space = tuning_space
        self._tuned: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self._active: Optional[Tuple[str, str]] = None
        names = tuple(static_argnames)
        if "interpret" not in names:
            names = names + ("interpret",)
        self._jit = jax.jit(
            kernel_fn, static_argnums=static_argnums or None, static_argnames=names
        )
        functools.update_wrapper(self, kernel_fn, updated=())

    # -- tuned-config state --------------------------------------------------

    def set_tuned(
        self,
        config: Dict[str, Any],
        *,
        chip: str = "",
        dtype: str = "",
        activate: bool = True,
    ) -> None:
        """Install a best-known config for (chip, dtype); ``activate`` makes
        it the one calls resolve (most-recent-tune-wins semantics)."""
        key = (chip, dtype)
        self._tuned[key] = dict(config)
        if activate:
            self._active = key

    def tuned_config(
        self, chip: Optional[str] = None, dtype: Optional[str] = None
    ) -> Optional[Dict[str, Any]]:
        """The active tuned config (no args), or the one for (chip, dtype)."""
        if chip is None and dtype is None:
            if self._active is None:
                return None
            return dict(self._tuned[self._active])
        cfg = self._tuned.get((chip or "", dtype or ""))
        return dict(cfg) if cfg is not None else None

    def clear_tuned(self) -> None:
        self._tuned.clear()
        self._active = None

    def load_tuned(self, **kw: Any):
        """Pick up a persisted TuningRecord for this kernel (zero timing);
        see :func:`repro.tuning.load_tuned` for the keyword surface."""
        from repro.tuning import load_tuned

        return load_tuned(self, **kw)

    @property
    def fingerprint_extra(self) -> str:
        """Behavioral state the artifact fingerprint must see: an active
        tuned config changes what a call lowers to."""
        if self._active is None:
            return ""
        cfg = self._tuned.get(self._active)
        return f"tuned:{sorted(cfg.items())!r}" if cfg else ""

    def _resolve_active(self, args: Tuple) -> Optional[Dict[str, Any]]:
        """The config to resolve for THIS call: prefer the entry tuned for
        the call's element type (a multi-dtype sweep leaves one config per
        dtype), falling back to the most recently activated one."""
        if self._active is None:
            return None
        chip, _ = self._active
        for a in args:
            dt = getattr(a, "dtype", None)
            if dt is not None:
                cfg = self._tuned.get((chip, canonical_dtype(dt)))
                if cfg is not None:
                    return cfg
                break
        return self._tuned.get(self._active)

    def _tuned_kwargs(self, args: Tuple, kw: Dict[str, Any]) -> Dict[str, Any]:
        """Merge the active tuned config into ``kw`` for keys the caller
        did not pass, after re-validating it against these arguments.

        Validation sees the call as it would actually execute: caller-passed
        axis values override the tuned ones (explicit kwargs win), and only
        the surviving tuned keys are merged.
        """
        cfg = self._resolve_active(args)
        if not cfg:
            return kw
        space = self.tuning_space
        if space is not None:
            view = {**cfg, **{k: v for k, v in kw.items() if k in space.axes}}
            extra = {
                k: v for k, v in kw.items()
                if k != "interpret" and k not in space.axes
            }
            valid = space.validate(view, args, extra=extra)
            if valid is None:  # the call's config does not fit: fall back
                return kw
            cfg = valid
        for k, v in cfg.items():
            kw.setdefault(k, v)
        return kw

    # -- call surfaces -------------------------------------------------------

    def __call__(self, *args: Any, **kw: Any):
        kw.setdefault("interpret", default_interpret())
        kw = self._tuned_kwargs(args, kw)
        return self._jit(*args, **kw)

    def kernel(self, *args: Any, **kw: Any):
        kw["interpret"] = False
        kw = self._tuned_kwargs(args, kw)
        return self._jit(*args, **kw)

    def interpret(self, *args: Any, **kw: Any):
        kw["interpret"] = True
        kw = self._tuned_kwargs(args, kw)
        return self._jit(*args, **kw)

    def lower(self, *args: Any, **kw: Any):
        """AOT-lower the jitted kernel (interpret mode only by default on
        the CPU backend, as for ``op(...)``).

        Exposing ``lower`` lets the analysis pipeline compile a kernel
        workload directly instead of re-wrapping it in ``jax.jit`` — which
        would turn the static arguments into tracers.  The active tuned
        config is resolved here too (``fingerprint_extra`` keeps the
        artifact store's content addresses distinct per config).
        """
        kw.setdefault("interpret", default_interpret())
        kw = self._tuned_kwargs(args, kw)
        return self._jit.lower(*args, **kw)

    def ref(self, *args: Any, **kw: Any):
        if self._ref is None:
            raise NotImplementedError(f"kernel {self.name!r} has no ref oracle")
        return self._ref(*args, **kw)

    def __repr__(self) -> str:
        if self._active is not None and self._tuned.get(self._active):
            chip, dtype = self._active
            cfg = " ".join(
                f"{k}={v}" for k, v in sorted(self._tuned[self._active].items())
            )
            where = f" @ {chip}/{dtype}" if (chip or dtype) else ""
            return f"KernelOps({self.name!r}, tuned[{cfg}]{where})"
        return f"KernelOps({self.name!r})"


KERNELS: Dict[str, KernelOps] = {}

# kernel workload builders, kept so registration can be re-applied after
# repro.analysis.clear_registry() (module import side effects only run once)
_WORKLOAD_BUILDERS: Dict[str, Callable[[], Workload]] = {}


def register_builtin_workloads() -> None:
    """(Re-)register every kernel workload; idempotent discovery hook."""
    for wl_name, builder in _WORKLOAD_BUILDERS.items():
        register_lazy(wl_name, builder, tags=("kernel",), replace=True)


def register_kernel(
    name: str,
    kernel: Optional[Callable] = None,
    *,
    ref: Optional[Callable] = None,
    static_argnums: Tuple[int, ...] = (),
    static_argnames: Tuple[str, ...] = (),
    workload: Optional[Callable[[], Workload]] = None,
    tuning_space: Optional[TuningSpace] = None,
):
    """Register a kernel entry point; usable directly or as a decorator.

    ``workload`` is a zero-arg builder returning the kernel's example
    Workload; it is registered lazily as ``kernel/<name>`` so importing the
    registry never constructs example arrays.  ``tuning_space`` declares
    the kernel's tunable static arguments for ``repro.tuning``.
    """

    def _do(fn: Callable) -> KernelOps:
        if name in KERNELS:
            raise ValueError(f"kernel {name!r} already registered")
        ops = KernelOps(
            name,
            fn,
            ref,
            static_argnums=static_argnums,
            static_argnames=static_argnames,
            tuning_space=tuning_space,
        )
        KERNELS[name] = ops
        if workload is not None:
            _WORKLOAD_BUILDERS[f"kernel/{name}"] = workload
            register_lazy(f"kernel/{name}", workload, tags=("kernel",),
                          replace=True)
        return ops

    if kernel is not None:
        return _do(kernel)
    return _do


def get_kernel(name: str) -> KernelOps:
    if name not in KERNELS:
        raise KeyError(f"unknown kernel {name!r}; have {sorted(KERNELS)}")
    return KERNELS[name]


def list_kernels() -> list:
    return sorted(KERNELS)


# ---------------------------------------------------------------------------
# The six kernel packages
# ---------------------------------------------------------------------------

from repro.kernels.flash_decode import kernel as _fd_k, ref as _fd_r  # noqa: E402
from repro.kernels.gemm import kernel as _gemm_k, ref as _gemm_r  # noqa: E402
from repro.kernels.jacobi2d import kernel as _jac_k, ref as _jac_r  # noqa: E402
from repro.kernels.qc_gate import kernel as _qc_k, ref as _qc_r  # noqa: E402
from repro.kernels.spmv import kernel as _spmv_k, ref as _spmv_r  # noqa: E402
from repro.kernels.stream import kernel as _stream_k, ref as _stream_r  # noqa: E402


def _gemm_workload() -> Workload:
    import jax.numpy as jnp

    n = 256
    fb = _gemm_r.flops_bytes(n, n, n, 4)

    def args():
        x = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.float32)
        y = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.float32)
        return (x, y)

    return Workload(
        name="kernel/gemm", fn=GEMM, args=args, dtype="fp32",
        flops=fb["flops"], hbm_bytes=fb["bytes"],
        problem=f"{n}^2", tags=("kernel",),
        notes="MXU-tiled Pallas GEMM; compute-bound Class 4",
    )


def _stream_workload() -> Workload:
    import jax.numpy as jnp

    rows, cols = 2048, 128
    fb = _stream_r.flops_bytes("triad", rows * cols, 4)

    def args():
        a = jnp.ones((rows, cols), jnp.float32)
        b = jnp.ones((rows, cols), jnp.float32)
        return (a, b, 3.0)

    return Workload(
        name="kernel/stream-triad", fn=STREAM_TRIAD, args=args, dtype="fp32",
        flops=fb["flops"], hbm_bytes=fb["bytes"],
        problem=f"{rows}x{cols}", tags=("kernel",),
        notes="McCalpin triad; streaming memory-bandwidth-bound Class 2",
    )


def _spmv_workload() -> Workload:
    import numpy as np

    n = 512

    def args():
        vals, cols, nnz = _spmv_r.make_problem(
            jax.random.PRNGKey(0), n, n, row_block=8, max_nnz=64, width_pad=128
        )
        x = jax.random.normal(jax.random.PRNGKey(1), (n,), vals.dtype)
        return (vals, cols, nnz, x)

    # per-nnz accounting (same model as spmv/ops.flops_bytes): 2 FLOPs per
    # nonzero; traffic = val + colidx + gathered x, the x reads being the
    # latency-bound pointer-chasing share
    nnz_np = np.asarray(
        _spmv_r.make_problem(
            jax.random.PRNGKey(0), n, n, row_block=8, max_nnz=64, width_pad=128
        )[2]
    )
    total_nnz = float(nnz_np.sum())
    return Workload(
        name="kernel/spmv", fn=SPMV, args=args, dtype="fp32",
        flops=2.0 * total_nnz, hbm_bytes=total_nnz * (4 + 4 + 4),
        gather_bytes=total_nnz * 4,
        problem=f"{n}^2 zipf", tags=("kernel",),
        notes="predicated block-ELL SpMV; pointer-chasing Class 3",
    )


def _jacobi_workload() -> Workload:
    import jax.numpy as jnp

    n = 256
    fb = _jac_r.flops_bytes(n, n, 4)

    def args():
        return (jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.float32),)

    return Workload(
        name="kernel/jacobi2d", fn=JACOBI_STEP, args=args, dtype="fp32",
        flops=fb["flops"], hbm_bytes=fb["bytes"],
        problem=f"{n}^2", tags=("kernel",),
        notes="5-point stencil sweep; memory-bound Class 2",
    )


def _qc_workload() -> Workload:
    import jax.numpy as jnp

    n_qubits = 14
    fb = _qc_r.flops_bytes(n_qubits, 4)

    def args():
        n_amp = 1 << n_qubits
        re = jnp.zeros((n_amp,), jnp.float32).at[0].set(1.0)
        im = jnp.zeros((n_amp,), jnp.float32)
        return (re, im)

    def one_gate(re, im):
        return RX_GATE(re, im, qubit=0, theta=0.25)

    return Workload(
        name="kernel/qc-gate", fn=one_gate, args=args, dtype="fp32",
        flops=fb["flops"], hbm_bytes=fb["bytes"],
        problem=f"{n_qubits} qubits", tags=("kernel",),
        notes="single RX gate over the state vector; streaming Class 2",
    )


def _flash_prefill_workload() -> Workload:
    import jax.numpy as jnp
    import numpy as np

    B, C, KV, G, D = 2, 16, 2, 4, 16
    bs, nb = 8, 8  # 64-token view per slot
    q_start = (24, 0)
    fb = _fd_r.prefill_flops_bytes(B, C, KV, G, D, q_start, dtype_bytes=4)

    def args():
        n_blocks = 1 + B * nb
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        q = jax.random.normal(ks[0], (B, C, KV, G, D), jnp.float32)
        kn = jax.random.normal(ks[1], (B, C, KV, D), jnp.float32)
        vn = jax.random.normal(ks[2], (B, C, KV, D), jnp.float32)
        kp = jax.random.normal(ks[3], (n_blocks, bs, KV, D), jnp.float32)
        vp = jax.random.normal(ks[4], (n_blocks, bs, KV, D), jnp.float32)
        bt = 1 + np.arange(B * nb, dtype=np.int32).reshape(B, nb)
        return (q, kn, vn, kp, vp, jnp.asarray(bt),
                jnp.asarray(q_start, jnp.int32))

    def one_chunk(q, kn, vn, kp, vp, bt, qs):
        return FLASH_PREFILL(q, kn, vn, kp, vp, bt, qs, block_c=8)[0]

    return Workload(
        name="kernel/flash-prefill", fn=one_chunk, args=args, dtype="fp32",
        flops=fb["flops"], hbm_bytes=fb["bytes"],
        problem=f"B{B} C{C} KV{KV} G{G} D{D} bs{bs}", tags=("kernel",),
        notes="chunked causal prefill committing K/V into paged blocks",
    )


def _flash_decode_workload() -> Workload:
    import jax.numpy as jnp

    B, KV, G, D, S = 2, 2, 4, 16, 64
    valid = (40, 64)
    fb = _fd_r.flops_bytes(B, KV, G, D, valid, dtype_bytes=4)

    def args():
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, KV, G, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
        vl = jnp.asarray(valid, jnp.int32)
        return (q, k, v, vl)

    def one_step(q, k, v, vl):
        return FLASH_DECODE(q, k, v, vl, block_s=16)

    return Workload(
        name="kernel/flash-decode", fn=one_step, args=args, dtype="fp32",
        flops=fb["flops"], hbm_bytes=fb["bytes"],
        problem=f"B{B} KV{KV} G{G} D{D} S{S}", tags=("kernel",),
        notes="predicated KV-cache attention decode; GQA reuse lifts AI",
    )


GEMM = register_kernel(
    "gemm", _gemm_k.gemm,
    ref=_gemm_r.gemm_ref,
    static_argnames=("bm", "bn", "bk"),
    workload=_gemm_workload,
    tuning_space=_spaces.gemm_space(),
)

STREAM_COPY = register_kernel(
    "stream-copy", _stream_k.stream_copy,
    ref=_stream_r.copy_ref,
    static_argnames=("block_rows",),
    tuning_space=_spaces.stream_space(n_arrays=1, flops_per_elem=0.0),
)
STREAM_SCALE = register_kernel(
    "stream-scale", _stream_k.stream_scale,
    ref=_stream_r.scale_ref,
    static_argnums=(1,), static_argnames=("block_rows",),
    tuning_space=_spaces.stream_space(n_arrays=1, flops_per_elem=1.0),
)
STREAM_ADD = register_kernel(
    "stream-add", _stream_k.stream_add,
    ref=_stream_r.add_ref,
    static_argnames=("block_rows",),
    tuning_space=_spaces.stream_space(n_arrays=2, flops_per_elem=1.0),
)
STREAM_TRIAD = register_kernel(
    "stream-triad", _stream_k.stream_triad,
    ref=_stream_r.triad_ref,
    static_argnums=(2,), static_argnames=("block_rows",),
    workload=_stream_workload,
    tuning_space=_spaces.stream_space(n_arrays=2, flops_per_elem=2.0),
)

SPMV = register_kernel(
    "spmv", _spmv_k.spmv_blockell,
    ref=_spmv_r.spmv_ref,
    static_argnames=("repeat",),
    workload=_spmv_workload,
)
SPMV_FIXED = register_kernel(
    "spmv-fixed-width", _spmv_k.spmv_fixed_width,
    ref=_spmv_r.spmv_ref,
)

JACOBI_STEP = register_kernel(
    "jacobi2d", _jac_k.jacobi_step,
    ref=_jac_r.jacobi_ref,
    static_argnames=("block_rows",),
    workload=_jacobi_workload,
    tuning_space=_spaces.jacobi2d_space(),
)

RX_GATE = register_kernel(
    "qc-gate", _qc_k.rx_gate,
    static_argnames=("qubit", "theta", "block_outer"),
    workload=_qc_workload,
    tuning_space=_spaces.qc_gate_space(),
)

FLASH_DECODE = register_kernel(
    "flash-decode", _fd_k.flash_decode,
    ref=_fd_r.decode_ref,
    static_argnames=("block_s",),
    workload=_flash_decode_workload,
    tuning_space=_spaces.flash_decode_space(),
)

FLASH_PREFILL = register_kernel(
    "flash-prefill", _fd_k.flash_prefill_paged,
    ref=_fd_r.prefill_paged_ref,
    static_argnames=("block_c", "block_s"),
    workload=_flash_prefill_workload,
    tuning_space=_spaces.flash_prefill_space(),
)
