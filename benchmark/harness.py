"""What every stage of a run shares: finding the files a cell names,
the device look, the compile cache, the profiler window, percentiles.

``run.py`` holds no per-cell code: a cell is ``BENCHMARK.json``'s entry
plus ``workloads/<cell>.json`` (runner, section, limits of ``correct``,
program options), ``configs/<config>.json``, ``traffic/<traffic>.json``,
``runners/<runner>.py``, ``reference/<config>.py`` and
``layer_metrics/<metric>.json`` with its reader.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import math
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts, root: str = ROOT):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: str = ROOT) -> dict:
    """Everything a cell names, found by name under ``root``."""
    manifest = load_json("BENCHMARK.json", root=root)
    entry = next((w for w in manifest["workloads"]
                  if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{[w['name'] for w in manifest['workloads']]}")
    bench = os.path.join(root, "benchmark")
    cell = load_json("workloads", f"{workload}.json", root=bench)
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == entry["config"])
    config = load_json(cfg_entry["file"], root=root)
    traffic = load_json("traffic", f"{entry['traffic']}.json", root=bench)
    metrics = []
    for m in manifest["per_layer"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        spec = load_json("layer_metrics", f"{m['name']}.json", root=bench)
        metrics.append({**m, **spec})
    end_to_end = [m for m in manifest["end_to_end"]
                  if "workloads" not in m or workload in m["workloads"]]
    return {"root": root, "bench": bench, "manifest": manifest,
            "entry": entry, "cell": cell, "config": config,
            "traffic": traffic, "end_to_end": end_to_end,
            "per_layer": metrics}


def load_reference(cell: dict):
    return load_module(
        os.path.join(cell["bench"], "reference",
                     f"{cell['entry']['config']}.py"),
        "benchmark_reference_" + cell["entry"]["config"].replace("-", "_"))


def load_runner(cell: dict):
    name = cell["cell"]["runner"]
    return load_module(os.path.join(cell["bench"], "runners", f"{name}.py"),
                       f"benchmark_runner_{name}")


def load_reader(cell: dict, name: str):
    return load_module(
        os.path.join(cell["bench"], "layer_metrics", "readers", f"{name}.py"),
        f"benchmark_reader_{name}")


def peaks_for(device_kind: str, root: str = ROOT) -> dict:
    table = load_json("benchmark", "peaks.json", root=root)["by_device_kind"]
    if device_kind not in table:
        raise SystemExit(f"device_kind {device_kind!r} is not in "
                         "benchmark/peaks.json; add it with its source")
    return table[device_kind]


def cache_dir(root: str = ROOT) -> str:
    """The compile cache: where ``JAX_COMPILATION_CACHE_DIR`` says, else
    ``<checkout>/.jax_cache`` — the program's own convention
    (``tpunet/utils/cache.py``), so both share one directory."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(root, ".jax_cache"))


def enable_cache(root: str = ROOT, program: bool = False) -> None:
    """Persistent compile cache for this process, every program cached
    (the program's own threshold of 1 s keeps hundreds of sub-second
    programs out, and each run is a new process)."""
    import jax

    if program:
        from tpunet.utils.cache import enable_persistent_compile_cache
        enable_persistent_compile_cache()
    else:
        jax.config.update("jax_compilation_cache_dir", cache_dir(root))
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def device_record() -> dict:
    import jax

    devs = jax.devices()
    peak = max(peak_bytes(d.memory_stats() or {}) for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def peak_bytes(stats: dict) -> int:
    """Peak device bytes, read when the window has closed: the bytes
    then in use plus the peak reserved for compiled programs'
    temporaries, or the allocator's own peak where that is larger.
    libtpu counts arrays and program temporaries apart (a MobileNetV2
    step at batch 128 shows 0.15 GB in use beside 6.29 GB reserved) and
    both are taken from the chip's memory; the allocator's peak alone
    is reached during set-up, before the step's program is loaded."""
    return max(int(stats.get("peak_bytes_in_use", 0)),
               int(stats.get("bytes_in_use", 0))
               + int(stats.get("peak_bytes_reserved", 0)))


def require_chips(chips: int, rehearse: bool = False) -> dict:
    """The device record of an accelerator with the chips the cell asks
    for and a row in the peak table; anything else ends the process
    with a non-zero code and no result. ``rehearse`` (tests only, never
    the command line) skips the look."""
    dev = device_record()
    if rehearse:
        return dev
    if dev["platform"] == "cpu":
        raise SystemExit(f"the benchmark measures an accelerator; JAX found "
                         f"{dev}")
    if dev["count"] < chips:
        raise SystemExit(f"cell needs {chips} chips, JAX found {dev}")
    peaks_for(dev["kind"])
    return dev


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of a non-empty list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


class TraceWindow:
    """A profiler window around part of a run: host ``TraceAnnotation``
    spans on, Python tracing off (it slows the host it measures)."""

    def __init__(self, directory: str):
        self.directory = directory
        self.path = None

    def __enter__(self):
        import jax

        os.makedirs(self.directory, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            self.directory, "**", "*.xplane.pb"), recursive=True))
        self.path = found[-1] if found else None
        return False


def say(*parts) -> None:
    """A line of the run's log (never the last line: that is the
    result)."""
    print("#", *parts, flush=True)


def write_json(path: str, obj) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)
