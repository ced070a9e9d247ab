"""The benchmark's own count of compilations, taken from JAX's
monitoring events and not from the program: every request to build an
executable (`requests`: a persistent-cache hit or a real compile), the
`hits` among them, and the seconds spent building (`build_s`: JAX's
backend-compile event, which also spans a hit's load from the cache).
A window in which `requests` moved compiled something."""
import threading

REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = {"requests": 0, "hits": 0, "build_s": 0.0}

    def install(self):
        """Register with jax.monitoring (once per process; listeners
        cannot be removed one by one, and a run is one process)."""
        from jax import monitoring
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def _on_event(self, event, **_):
        key = {REQUEST: "requests", HIT: "hits"}.get(event)
        if key:
            with self._lock:
                self._n[key] += 1

    def _on_duration(self, event, seconds, **_):
        if event == BACKEND_COMPILE:
            with self._lock:
                self._n["build_s"] += seconds

    def snapshot(self):
        with self._lock:
            return dict(self._n)

    def delta(self, before):
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}
