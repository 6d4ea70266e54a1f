"""The part of a run that touches JAX: finding the chips, the compile
cache, the measured window (with the profiler around it in a ``--trace 1``
run) and the result line's ``device``."""
from __future__ import annotations

import contextlib
import os
import shutil
import time

from . import harness, tracing

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_DIR = harness.ROOT / "bench_out" / "trace"


def accelerators(chips: int) -> list:
    """The first ``chips`` TPU devices; no TPU, or too few, is an error."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise harness.BenchError(
            f"no TPU found (JAX sees {devices[0].platform} devices); the "
            "benchmark never falls back to the CPU")
    if len(devices) < chips:
        raise harness.BenchError(f"the cell asks for {chips} chips, JAX "
                                 f"sees {len(devices)}")
    return devices[:chips]


class CompileCounter:
    """Counts XLA backend compiles as JAX's monitoring events report them
    (a persistent-cache hit is not one)."""

    def __init__(self):
        self.count = 0

    def __call__(self, name: str, secs: float, **_) -> None:
        if name == COMPILE_EVENT:
            self.count += 1


def enable_compile_cache() -> None:
    import jax
    from repro.launch.cache import enable_compile_cache as program_cache

    # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache; JAX writes
    # no entry into a directory that is not there
    os.makedirs(program_cache(), exist_ok=True)
    # cache every program, however quick to compile, so that only a
    # checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class Session:
    """One run of one cell: the generator of the cell's traffic builds the
    deployment, opens :meth:`window` when set-up is done, and hands
    back what it measured."""

    def __init__(self, cell: harness.Cell, seed: int, seconds: float,
                 trace: bool, devices: list, t_start: float):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.devices, self.t_start = trace, devices, t_start
        self.run_record = harness.Run(config=cell.config, traffic=cell.traffic,
                                      chips=len(devices))
        self.checks = harness.Checks()
        self.compiles = CompileCounter()
        self._window_compiles = None

    # -- used by generators -----------------------------------------------------
    def span(self, name: str):
        """A host span in the profiler's trace (``bench.<name>``) when
        tracing, else nothing: the untraced run carries no annotation."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(tracing.SPAN_PREFIX + name)

    @contextlib.contextmanager
    def window(self):
        """Around the measured loop: set-up ends where it opens; compiles
        inside it are counted; a traced run profiles it."""
        import jax

        self.run_record.setup_s = time.perf_counter() - self.t_start
        before = self.compiles.count
        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # host spans, not every call
            options.enable_hlo_proto = False
            jax.profiler.start_trace(str(TRACE_DIR),
                                     profiler_options=options)
        try:
            with self.span("window"):
                yield
        finally:
            if self.trace:
                jax.profiler.stop_trace()
            self._window_compiles = self.compiles.count - before

    # -- the run -------------------------------------------------------------
    def run(self) -> tuple[str, harness.Checks]:
        import jax

        kind = self.devices[0].device_kind
        self.run_record.peaks = harness.peaks_for(kind)
        enable_compile_cache()
        jax.monitoring.register_event_duration_secs_listener(self.compiles)
        generator = harness.generator_module(self.cell.traffic)
        out = generator.run(self)
        run = self.run_record
        if self._window_compiles is None:
            raise harness.BenchError("the generator measured no window")
        self.checks.add("window_compiles", self._window_compiles, 0)
        device = {"platform": self.devices[0].platform, "kind": kind,
                  "count": len(self.devices),
                  "memory_peak_bytes": out["memory_peak_bytes"]}
        breakdown = None
        if self.trace:
            planes = tracing.load_planes(tracing.find_xplane(TRACE_DIR))
            run.trace = tracing.reduce(planes, len(self.devices))
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
            breakdown = tracing.breakdown(run.trace)
            metrics = harness.read_metrics(self.cell.per_layer, run, False)
        else:
            metrics = harness.read_metrics(self.cell.end_to_end, run, True)
        line = harness.result_line(self.checks.correct, out["attempted"],
                                   out["failed"], metrics, device,
                                   self.checks, breakdown)
        return line, self.checks
