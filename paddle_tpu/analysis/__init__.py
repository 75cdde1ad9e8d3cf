"""paddle_tpu.analysis — static analysis of the framework and its
compiled programs (ISSUE 13).

Submodules (all pure stdlib, importable with jax blocked — the same
discipline as paddle_tpu.obs, enforced by the jax_import_fence pass):

- `hlo_text`        compiled-HLO text parser + op classifier (shared
                    with tools/trace_attribution.py)
- `hlo_audit`       compiled-program auditor: donation/aliasing,
                    host-transfer budgets, byte budgets, forbidden-op
                    patterns, driven by tools/traces/audit_budgets.json
- `recompile_guard` jit-cache-miss tracker armed after warmup by the
                    trainer and serving batcher
- `ast_lint`        source-level pass registry (jax-import fence,
                    duplicate dict keys, unfenced timing, unlocked
                    mutation)
- `lock_order`      named-lock instrumentation + inversion detection
                    (the faults shard runs with PADDLE_LOCK_CHECK=1)

Driver: `python tools/framework_lint.py --all`.
"""

from __future__ import annotations

_SUBMODULES = (
    "ast_lint", "hlo_audit", "hlo_text", "lock_order",
    "recompile_guard",
)

__all__ = list(_SUBMODULES)


def __getattr__(name):  # PEP 562: lazy submodule access
    if name in _SUBMODULES:
        import importlib

        return importlib.import_module(f"{__name__}.{name}")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
