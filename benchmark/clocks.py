"""What the harness counts about the process itself: compilations (JAX's
own monitoring events) and Pallas calls traced in interpret mode.  Both
are copies of ``chip_smoke.py``'s classes."""

from __future__ import annotations


class CompileClock:
    """Sums JAX's lowering and backend-compile durations.  ``compile_s`` is
    small when the persistent compilation cache is warm."""

    EVENTS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
              "/jax/core/compile/backend_compile_duration": "compile_s"}

    def __init__(self):
        import jax.monitoring

        self.totals = dict.fromkeys(self.EVENTS.values(), 0.0)
        self.compilations = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        name = self.EVENTS.get(event)
        if name is not None:
            self.totals[name] += duration
            self.compilations += name == "compile_s"

    def now(self):
        return dict(self.totals, compilations=self.compilations)

    def since(self, mark):
        now = self.now()
        return {k: now[k] - mark[k] for k in now}


class PallasCallLog:
    """Records every ``pallas_call`` traced while installed: the kernel
    body's name and whether it was asked to run in interpret mode."""

    def __init__(self):
        self.calls = []

    def __enter__(self):
        from jax.experimental import pallas as pl

        self._pl, self._orig = pl, pl.pallas_call

        def logged(kernel, *args, **kwargs):
            body = getattr(kernel, "func", kernel)
            self.calls.append({"kernel": getattr(body, "__name__", str(body)),
                               "interpret": bool(kwargs.get("interpret", False))})
            return self._orig(kernel, *args, **kwargs)

        pl.pallas_call = logged
        return self

    def __exit__(self, *exc):
        self._pl.pallas_call = self._orig

    def interpreted(self):
        return sorted({c["kernel"] for c in self.calls if c["interpret"]})

    def compiled(self):
        return sorted({c["kernel"] for c in self.calls if not c["interpret"]})
