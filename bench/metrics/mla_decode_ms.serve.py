"""Latent attention (models/attention.py, the ``mla`` scope): device
milliseconds per execution of the jitted decode step spent in operations
under the program's ``mla`` named scope, from the profiler trace and the
decode step's compiled HLO (``runners/serve_waves_scoped.py``)."""


def read(data):
    ms = (data.get("scopes") or {}).get("ms_per_step") or {}
    return ms.get("mla")
