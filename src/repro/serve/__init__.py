"""Serving over the CuPBoP-JAX runtime.

:mod:`repro.serve.kernel_service` is the kernel-launch tier: multi-tenant
requests against registered suite kernels, batched into stacked dispatches
(``docs/serving.md``).
"""
from repro.serve.kernel_service import (
    Endpoint,
    KernelService,
    ServeTicket,
    ServiceClosed,
    ServiceError,
    ServiceOverloaded,
    ServiceStats,
    ServiceTimeout,
)

__all__ = [
    "Endpoint", "KernelService", "ServeTicket", "ServiceClosed",
    "ServiceError", "ServiceOverloaded", "ServiceStats", "ServiceTimeout",
]
