"""Execution-runtime services: fault tolerance, watchdogs, snapshot/resume,
atomic model publish/subscribe, and the continuous-training service loop.

This package holds the machinery that keeps long runs alive on flaky
platforms — it deliberately imports neither jax nor any other heavy
dependency at module scope, so the CLI entry and the fleet controller
can use it before (or instead of) binding an accelerator platform.
(`continuous` and `serving` are not imported here: they pull numpy and,
lazily, the model stack; import them explicitly where a service loop or
a serving runtime is actually being run.)
"""
from . import publish  # noqa: F401
from . import resilience  # noqa: F401
from . import telemetry  # noqa: F401
from . import tracing  # noqa: F401
from . import warmup  # noqa: F401
from . import xla_obs  # noqa: F401

#: the observability surface (ISSUE 9): `from lightgbm_tpu.runtime import
#: obs` is the supported spelling for metrics/span/exporter access —
#: obs.REGISTRY, obs.span(...), obs.start_http_server(...),
#: obs.METRIC_TABLE.
obs = telemetry

__all__ = ["resilience", "publish", "telemetry", "obs", "tracing",
           "warmup", "xla_obs"]
