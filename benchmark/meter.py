"""CompileMeter: backend compilations, persistent-cache hits and
writes, from JAX's own monitoring events.  A copy of chip_smoke.py's,
with cache writes added (a write is a cold build of a program large
enough to be kept) and a count of the builds that took a second or
more: a program of the level loop compiled or loaded from the cache,
as against the millisecond jits of a new slice shape."""

SLOW_BUILD_S = 1.0


class CompileMeter:
    def __init__(self):
        import jax.monitoring as mon
        self.n = self.slow = self.hits = self.writes = 0
        self.secs = 0.0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.slow += secs >= SLOW_BUILD_S
            self.secs += secs

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def snapshot(self):
        return {"compiles": self.n, "slow_builds": self.slow,
                "cache_hits": self.hits,
                "cache_writes": self.writes, "compile_s": self.secs}

    def since(self, before):
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}
