"""What both runners share: the program's configuration built from a
configuration file, the compile cache and its counters, the profiler window
and the printed comparison of each checked number with its limit."""

from __future__ import annotations

import contextlib
import os
import shutil


def program_config(cfg: dict, **kw):
    """The program's TransformerConfig from a configuration file: its
    ``program_fields`` maps each field of the program's config to the
    published key that holds it (``config.json`` names)."""
    from accelerate_tpu.models import TransformerConfig

    return TransformerConfig(
        **{field: cfg[key] for field, key in cfg["program_fields"].items()}, **kw)


def modules_of(cfg: dict):
    """The plain reference and the seeded weights a configuration names."""
    from . import cell

    return cell.named(cfg["reference"]), cell.named(cfg["weights"])


def activate_cache():
    """The repo's one cache rule (JAX_COMPILATION_CACHE_DIR if set, else
    <checkout>/.jax_compile_cache), persisting EVERY compile so that a second
    run finds each program of the first. Returns (dir, monitor)."""
    from accelerate_tpu.compilation import (
        activate_persistent_cache,
        get_compile_monitor,
    )
    from accelerate_tpu.utils.dataclasses import CompilePlugin

    monitor = get_compile_monitor()  # listeners on before the first compile
    path = activate_persistent_cache(CompilePlugin(
        cache_min_compile_time_secs=0.0, cache_min_entry_size_bytes=-1))
    return path, monitor


def compiles_in(delta: dict) -> int:
    """Programs that were compiled or loaded from the persistent cache: each
    is a program the warm-up did not cover."""
    return int(delta.get("persistent_cache_hits", 0)
               + delta.get("persistent_cache_misses", 0))


class Tracer:
    """The traced window of a ``--trace 1`` run: the last few seconds of the
    measured window, written under the checkout at a fixed path."""

    def __init__(self, cell: dict, enabled: bool):
        self.enabled = enabled
        self.dir = os.path.join(os.path.dirname(cell["bench_dir"]),
                                ".bench_trace", cell["name"])
        self.state = "idle"
        self.length = float(cell["spec"].get("trace_seconds", 3.0))

    def span(self, name: str):
        """A host span on the profiler's clock; free when not tracing."""
        if self.state != "on":
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench:" + name)

    def tick(self, elapsed_s: float, seconds: float) -> None:
        """Called between units of work: the profiler starts ``length``
        seconds before the window closes (starting takes ~0.05 s) and the
        runner stops it after the close — stopping stalls the host for
        seconds, which inside the window would pile the arrivals up."""
        if not self.enabled or self.state != "idle":
            return
        if elapsed_s >= seconds - self.length:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.state = "on"

    def stop(self) -> None:
        if self.state == "on":
            import jax

            jax.profiler.stop_trace()
            self.state = "done"

    def result(self):
        if self.state != "done":
            return None
        from . import trace_reduce

        try:
            return trace_reduce.reduce(trace_reduce.find_xplane(self.dir))
        except ValueError as exc:  # e.g. a CPU rehearsal: no device plane
            print(f"trace not reduced: {exc}", flush=True)
            return None


class Checks:
    """Each number compared, printed beside its limit; ``ok`` is their and."""

    def __init__(self, say):
        self.say = say
        self.ok = True
        self.rows = []

    def limit(self, name: str, value: float, limit: float) -> None:
        good = value <= limit and value == value  # NaN fails
        self.rows.append((name, value, limit, good))
        self.say(f"check {name}: {value:.6g} (limit {limit:.6g}) "
                 f"{'ok' if good else 'FAILED'}")
        self.ok = self.ok and good

    def exact(self, name: str, value, want) -> None:
        good = value == want
        self.rows.append((name, value, want, good))
        self.say(f"check {name}: {value} (must be {want}) "
                 f"{'ok' if good else 'FAILED'}")
        self.ok = self.ok and good


def memory_peak_bytes_of(jax) -> int:
    """Peak bytes in use on the fullest chip, where the backend reports it
    (read before the program is freed and the reference runs)."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.local_devices()]
    return max(peaks) if peaks else 0
