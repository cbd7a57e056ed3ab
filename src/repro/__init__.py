"""VeilGraph reproduction — streaming approximate graph processing on JAX.

The public front door lives in :mod:`repro.api`; ``repro.session`` et al.
are re-exported lazily here so ``import repro`` stays cheap for the
subpackages (kernels/launch/analysis) that import no engine.
"""

_API_NAMES = ("session", "serve_session", "VeilGraphSession", "QueryResult",
              "Action", "available_algorithms")


def __getattr__(name):
    if name in _API_NAMES:
        from repro import api
        return getattr(api, name)
    raise AttributeError(f"module 'repro' has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_API_NAMES))
