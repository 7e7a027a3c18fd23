"""What every cell shares: finding a cell's files by name, the device, the
compile cache, host spans, compile counting and the per-layer readers."""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import os
import resource
import sys
import threading
import time
import traceback
from contextlib import contextmanager, nullcontext
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def log(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_file(path: Path):
    """The Python module in the file ``path``, loaded once per process: a
    model module is a static argument of the reference's jitted steps, so
    one module object keeps their compiled programs."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_model(config: dict, root: Path = ROOT):
    """The plain reference of ``config["model"]``:
    ``bench/models/<model>.py``, which defines ``dims``, ``init_weights``,
    ``forward`` and ``train_counts`` (``bench/README.md``)."""
    rel = f"bench/models/{config['model']}.py"
    if not (root / rel).is_file():
        raise SystemExit(f"configuration {config['name']!r} names model "
                         f"{config['model']!r}, which has no reference: "
                         f"add {rel}")
    return load_file(root / rel)


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration, its
    model's reference module, traffic mix, limits and the metrics it
    reports."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r} (known: {sorted(cells)})")
    cell = cells[name]
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = load_json(root / entry["file"])

    def applies(m):
        return name in m.get("workloads", [name])
    return {
        "name": name,
        "chips": cell["chips"],
        "config": config,
        "model": load_model(config, root),
        "traffic": load_json(root / "bench" / "traffic"
                             / f"{cell['traffic']}.json"),
        "limits": load_json(root / "bench" / "limits" / f"{name}.json"),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
    }


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` where set,
    else the fixed ``bench/.jax_cache`` inside the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / "bench" / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


class Compiles:
    """Counts executables built or loaded (JAX's backend-compile event)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


class Spans:
    """``bench.<name>`` host spans in the profiler trace; free when off."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def wrap(self, fn, name: str):
        if not self.on:
            return fn

        def wrapped(*a, **kw):
            with self(name):
                return fn(*a, **kw)
        return wrapped


class StallWatch:
    """Where the driving thread is when a call runs ``stall_s`` or longer.

    ``watch(name)`` wraps one call.  A watcher thread, waking every
    ``POLL_S``, samples the driving thread's stack once the call has run
    ``stall_s``; each such call keeps its wall time, the thread's own CPU
    time, the collector's time, page faults and context switches (the
    thread's ``getrusage``), so a stall reads as computing, waiting,
    collecting or pre-empted.  ``close()`` stops and joins the thread."""

    POLL_S = 0.1

    def __init__(self, stall_s: float = 0.5):
        self.stall_s = stall_s
        self.stalls, self.gc_s, self.gc_n = [], [0.0] * 3, [0] * 3
        self.tracked = len(gc.get_objects())
        self._gc_t0 = None
        self._call = None               # (name, start, stack sample)
        self._tid = threading.get_ident()
        self._stop = threading.Event()
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._thread.start()

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s[info["generation"]] += time.perf_counter() - self._gc_t0
            self.gc_n[info["generation"]] += 1
            self._gc_t0 = None

    def _watch(self):
        while not self._stop.wait(self.POLL_S):
            call = self._call
            if call and call[2] is None and \
                    time.perf_counter() - call[1] >= self.stall_s:
                frame = sys._current_frames().get(self._tid)
                stack = traceback.extract_stack(frame)[-8:] if frame else []
                call[2] = [f"{Path(f.filename).name}:{f.lineno} {f.name}"
                           for f in stack]

    @staticmethod
    def _usage():
        r = resource.getrusage(resource.RUSAGE_THREAD)
        return (r.ru_utime + r.ru_stime, r.ru_majflt, r.ru_minflt,
                r.ru_nvcsw, r.ru_nivcsw)

    @contextmanager
    def watch(self, name: str):
        u0, g0 = self._usage(), sum(self.gc_s)
        self._call = call = [name, time.perf_counter(), None]
        try:
            yield
        finally:
            self._call = None
            wall = time.perf_counter() - call[1]
            if wall >= self.stall_s:
                u = [b - a for a, b in zip(u0, self._usage())]
                self.stalls.append({
                    "call": name, "wall_s": wall, "cpu_s": u[0],
                    "gc_s": sum(self.gc_s) - g0, "majflt": u[1],
                    "minflt": u[2], "vcsw": u[3], "ivcsw": u[4],
                    "stack": call[2]})

    def close(self) -> None:
        self._stop.set()
        self._thread.join()
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def report(self, log) -> None:
        log(f"[stalls] {len(self.stalls)} calls of {self.stall_s} s or "
            f"more; collector {sum(self.gc_s):.3f} s in {self.gc_n} "
            f"collections by generation; {self.tracked} objects tracked "
            f"at the start")
        for st in self.stalls:
            log("[stall] " + json.dumps(st))


@contextmanager
def profiled(trace_dir: Path, on: bool):
    """Profile the block (Python tracer off) when ``on``."""
    if not on:
        yield
        return
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def reader(name: str, root: Path = ROOT):
    """The reader of the per-layer metric ``name``:
    ``bench/metrics/<name>.py``, with ``read(ctx)`` and, where it reads
    kernel times from the trace, ``KERNELS``."""
    return load_file(root / "bench" / "metrics" / f"{name}.py")


def kernels_read(metrics, root: Path = ROOT) -> tuple:
    """The kernels whose device time the readers of ``metrics`` read: the
    union of their ``KERNELS``."""
    return tuple(sorted({k for m in metrics
                         for k in getattr(reader(m["name"], root),
                                          "KERNELS", ())}))


def read_per_layer(metrics, ctx: dict, root: Path = ROOT) -> dict:
    """Each per-layer metric from its reader; a reader that finds nothing
    returns None and its metric is left out."""
    out = {}
    for m in metrics:
        v = reader(m["name"], root).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def gnn_config(config: dict, traffic: dict):
    """The program's config for this cell: every key of the configuration
    file that names a ``GNNConfig`` field (lists as tuples), ``lr`` from
    its optimizer, and the A3GNN knobs from the traffic mix."""
    from repro.configs.gnn import GNNConfig
    fields = {f.name for f in dataclasses.fields(GNNConfig)}
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in config.items() if k in fields}
    knobs = {k: traffic[k] for k in (
        "bias_rate", "cache_volume_mb", "cache_policy", "sampling_device",
        "fused_gather_agg", "workers", "parallel_mode") if k in traffic}
    return GNNConfig(**{**kw, "lr": config["optimizer"]["lr"], **knobs})


def make_graph(config: dict, arrays: dict):
    from repro.graph.storage import Graph
    return Graph(name=config["name"], **arrays)


def now() -> float:
    return time.perf_counter()


def stderr(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
