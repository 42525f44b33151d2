"""Counts XLA compiles (persistent-cache reads included) and their seconds
through ``jax.monitoring``, so a run can say how many happened inside its
window."""
from __future__ import annotations


class CompileLog:

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.by_name = {}               # program name -> compile seconds
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, secs, fun_name="", **_):
            if name == event:
                self.n += 1
                self.seconds += secs
                self.by_name[fun_name] = self.by_name.get(fun_name, 0) + secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return self.n, self.seconds, self.cache_hits

    def since(self, mark) -> str:
        n, s, h = mark
        return (f"compiles={self.n - n} compile_s={self.seconds - s:.3f} "
                f"persistent_cache_hits={self.cache_hits - h}")
