"""Shared ``REPRO_QUANT_KERNEL`` platform dispatch for the Pallas kernels.

Every fused kernel family in the data plane — the wNa16 GEMM
(:mod:`repro.kernels.wna16_gemm`), paged decode attention, and the
chunk-prefill block walk (:mod:`repro.kernels.paged_attention`) — resolves
its execution path through this one module instead of re-implementing the
env-var / backend logic per call site:

  * ``auto``             — compiled Pallas on TPU, XLA fallback elsewhere
  * ``pallas``           — compiled Pallas (Mosaic) unconditionally
  * ``pallas_interpret`` — Pallas interpret mode (kernel-body validation on
                           CPU; used by the parity/token-identity tests and
                           the ``pallas_interpret`` CI matrix leg)
  * ``xla``              — the pure-XLA fallback path of the kernel family
                           (packed-dequant matmul for wNa16; the bucketed
                           jnp gather for paged/chunk attention — also the
                           numerically pinned parity oracle)

The mode is read at trace time — set it before building jitted callables
(the engine's per-instance jit caches make this safe per engine). The env
var is only the initial value; :func:`set_mode` overrides it at runtime.
"""
from __future__ import annotations

import os

import jax

MODES = ("auto", "pallas", "pallas_interpret", "xla")

_mode = os.environ.get("REPRO_QUANT_KERNEL", "auto")


def set_mode(mode: str) -> str:
    """Set the global dispatch mode; returns the previous mode."""
    global _mode
    if mode not in MODES:
        raise ValueError(f"unknown REPRO_QUANT_KERNEL mode {mode!r}; "
                         f"expected one of {MODES}")
    prev = _mode
    _mode = mode
    return prev


def mode() -> str:
    """The raw (unresolved) dispatch mode, possibly ``auto``."""
    return _mode


def resolve(m: str = None, backend: str = None) -> str:
    """Resolve a dispatch mode to one of pallas | pallas_interpret | xla.

    ``m`` defaults to the global mode; ``backend`` to
    ``jax.default_backend()`` (only consulted for ``auto``).
    """
    m = _mode if m is None else m
    if m not in MODES:
        raise ValueError(f"unknown REPRO_QUANT_KERNEL mode {m!r}; "
                         f"expected one of {MODES}")
    if m == "auto":
        backend = backend or jax.default_backend()
        return "pallas" if backend == "tpu" else "xla"
    return m


def uses_pallas(m: str = None, backend: str = None) -> bool:
    """True when the resolved mode runs a Pallas kernel body
    (compiled or interpret) rather than the XLA fallback."""
    return resolve(m, backend) != "xla"


def interpret(m: str = None, backend: str = None) -> bool:
    """True when Pallas kernels should run in interpret mode."""
    return resolve(m, backend) == "pallas_interpret"


def kernel_interpret(interpret: bool = None) -> bool:
    """The ``interpret`` flag of a direct call to a Pallas kernel entry
    point. An explicit flag wins; ``None`` resolves the dispatch mode:
    compiled under ``pallas`` (``auto`` on TPU), interpreted under
    ``pallas_interpret``. Under ``xla`` there is no kernel to run — the XLA
    path of each family lives behind :mod:`repro.kernels.ops`, so asking a
    kernel entry point for it is an error rather than a silent interpret."""
    if interpret is not None:
        return interpret
    m = resolve()
    if m == "xla":
        raise ValueError(
            "REPRO_QUANT_KERNEL resolves to 'xla', which has no Pallas "
            "kernel; call the repro.kernels.ops entry point for the XLA "
            "path, or pass interpret= explicitly")
    return m == "pallas_interpret"


def kv_dequant_path(m: str = None, backend: str = None) -> str:
    """Where quantized-KV blocks get dequantized for the resolved mode.

    ``"kernel"`` — inside the Pallas block walk (the wNa16-style FMA
    epilogue per visited block, no materialized fp copy); ``"explicit"`` —
    the xla gather oracle dequantizes the whole pool up front before the
    gather. Both consume the same sidecar bundle and must agree
    numerically (parity-tested in both CI matrix legs).
    """
    return "kernel" if uses_pallas(m, backend) else "explicit"
