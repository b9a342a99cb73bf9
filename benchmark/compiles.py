"""Counts what the compiler did, through ``jax.monitoring``.

JAX records one ``backend_compile_duration`` event for every program it
needs an executable for.  The event wraps the look-up in the persistent
compilation cache, so it fires for a program that was only loaded too; the
``cache_hits`` event, recorded inside it, tells the two apart.  The counter
keeps both: ``requests`` (executables asked for), ``loaded`` (served from
the persistent cache) and so ``compiled = requests - loaded``, each with its
seconds.  (Copied in spirit from ``chip_smoke.py::CompileCounter``, which
counts requests only.)
"""

_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    def __init__(self):
        import jax.monitoring

        self.requests = self.loaded = 0
        self.request_s = self.load_s = 0.0
        self._hit_pending = False
        self.compiled_names = []
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_time)

    def _on_event(self, event, **_):
        if event == _HIT:
            self._hit_pending = True

    def _on_time(self, event, duration, **kw):
        if event != _COMPILE:
            return
        self.requests += 1
        self.request_s += duration
        if self._hit_pending:
            self.loaded += 1
            self.load_s += duration
            self._hit_pending = False
        else:
            self.compiled_names.append(str(kw.get("fun_name", "?")))

    def mark(self):
        return (self.requests, self.loaded, self.request_s, self.load_s,
                len(self.compiled_names))

    def since(self, mark):
        """What happened after ``mark``: requests, loaded, compiled, and
        the seconds of all requests and of the compiled ones."""
        req, hit = self.requests - mark[0], self.loaded - mark[1]
        req_s, hit_s = self.request_s - mark[2], self.load_s - mark[3]
        return {"requests": req, "loaded": hit, "compiled": req - hit,
                "request_s": req_s, "compile_s": req_s - hit_s,
                "compiled_names": self.compiled_names[mark[4]:][:16]}
