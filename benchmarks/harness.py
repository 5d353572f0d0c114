"""What every runner shares: finding a cell's files by name, the look for
the chip, the compile cache, counting compilations, tracing a window,
reading the per-layer metrics, and the one result line.

Everything that belongs to one configuration, one cell, one runner, one
kernel or one per-layer metric is a file of its own, found by the name in
`BENCHMARK.json`; nothing here names any of them.
"""
from __future__ import annotations

import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
T0 = time.perf_counter()  # process start, as near as this module sees it


# ------------------------------------------------------------------ files
# where a cell's files are looked for, first hit wins; a test that adds a
# throw-away cell puts its own directory in front
SEARCH = [HERE]


def find(*parts) -> str:
    for base in SEARCH:
        path = os.path.join(base, *parts)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no {os.path.join(*parts)} under {SEARCH}")


def load_json(*parts):
    with open(find(*parts)) as f:
        return json.load(f)


def load_benchmark(root=ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`benchmarks/<kind>/<name>.py`, by the name a data file gives."""
    path = find(kind, name + ".py")
    mod_name = "benchmarks.%s.%s" % (kind, name.replace(".", "_"))
    if getattr(sys.modules.get(mod_name), "__file__", None) == path:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of `workloads`, with its files read."""

    def __init__(self, bench: dict, name: str, root: str = ROOT):
        entries = [w for w in bench["workloads"] if w["name"] == name]
        if not entries:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.entry = entries[0]
        self.name = name
        self.bench = bench
        self.spec = load_json("workloads", name + ".json")
        cfg_entry = [c for c in bench["configs"]
                     if c["name"] == self.entry["config"]][0]
        with open(os.path.join(root, cfg_entry["file"])) as f:
            self.config = json.load(f)
        self.traffic = self.spec["traffic"]
        self.chips = int(self.entry["chips"])
        self.limits = self.spec["limits"]

    def reports(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"] if self.reports(m)]

    def per_layer(self) -> list:
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if self.reports(m) and m["moves"] in mine]


# ----------------------------------------------------------------- device
def require_chips(count: int) -> dict:
    """The device as JAX reports it; the end of the run where that is no
    TPU or fewer chips than the cell needs (no result line is printed)."""
    import jax

    devs = jax.devices()
    found = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if found["platform"] != "tpu" or found["count"] < count:
        raise SystemExit(f"this cell needs {count} TPU chip(s); JAX found "
                         f"{found}")
    return found


def peaks_of(device_kind: str) -> dict:
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"benchmarks/peaks.json")
    return table[device_kind]


def place_compile_cache() -> str:
    """JAX's persistent cache at `JAX_COMPILATION_CACHE_DIR`, else at a
    fixed path inside the checkout; every program is kept."""
    import jax

    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCounter:
    """Counts backend compilations (cache hits are not compilations)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += seconds


def memory_peak_bytes(temp_bytes: int) -> int:
    """The peak the fullest chip really holds: the allocator's peak plus
    the largest temp of the cell's executables, which this runtime's
    `peak_bytes_in_use` leaves out (PERF.md section 3)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks) + temp_bytes)


# ------------------------------------------------------------------ trace
class TracedWindow:
    """Profiles what runs between `start()` and `stop()`, on the host and
    the device; `reduce()` gives `trace_reduce.reduce_xplane` of it and
    removes the files.  With ``on`` false every method does nothing."""

    SPAN = "bench_window"

    def __init__(self, on: bool):
        self.on = on
        self.running = False
        self.dir = None
        self.span = None
        self._stopping = None

    def start(self):
        if not self.on:
            return
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation(self.SPAN)
        self.span.__enter__()
        self.running = True

    def stop(self, aside: bool = False):
        """End the window's span and collect the profile; with ``aside``
        the collecting goes to a thread of its own, for a caller that must
        not stall meanwhile (a load generator): `reduce()` waits for it."""
        if not self.running:
            return
        import threading

        import jax

        self.running = False
        self.span.__exit__(None, None, None)
        if aside:
            self._stopping = threading.Thread(target=jax.profiler.stop_trace)
            self._stopping.start()
        else:
            jax.profiler.stop_trace()

    def reduce(self, keep_to=None):
        if not self.on:
            return None
        from benchmarks import trace_reduce

        self.stop()
        if self._stopping is not None:
            self._stopping.join()
        try:
            (path,) = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                                recursive=True)
            if keep_to:
                os.makedirs(os.path.dirname(os.path.abspath(keep_to)),
                            exist_ok=True)
                shutil.copy(path, keep_to)
            return trace_reduce.reduce_xplane(path, window_span=self.SPAN)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(name: str):
    """A host span in the profiler's own trace (free when none is on)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------- results
def read_layer_metrics(cell: Cell, ctx: dict) -> dict:
    """Each of the cell's per-layer metrics through its own reader.  A
    reader that finds nothing to read returns None and the metric is left
    out of the line."""
    out = {}
    for m in cell.per_layer():
        value = load_module("layer_metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def judge(compared: dict) -> bool:
    """``compared`` is name -> [number, limit]; correct where every number
    is within its limit (a NaN is not)."""
    return all(v == v and v <= lim for v, lim in compared.values())


def emit_result(cell: Cell, *, trace: bool, device: dict, end_to_end: dict,
                layer: dict, attempted: int, failed: int, compared: dict,
                breakdown=None, notes=None):
    """The run's last words: the numbers compared beside their limits on
    standard error, then the one result line on standard output."""
    correct = judge(compared) and failed == 0
    units = {m["name"]: m["unit"] for m in cell.end_to_end()}
    metrics = layer if trace else {
        k: {"value": float(v), "unit": units[k]}
        for k, v in end_to_end.items() if k in units}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if trace and breakdown:
        line["breakdown"] = breakdown
    if notes:
        line["notes"] = notes
    line["compared"] = {k: {"value": float(v), "limit": float(lim)}
                        for k, (v, lim) in compared.items()}
    sys.stdout.flush()
    for k, (v, lim) in compared.items():
        print(f"compared {k}: {v:.6g} (limit {lim:g})"
              f"{'' if v == v and v <= lim else '  <-- OVER'}",
              file=sys.stderr)
    print(f"correct: {correct} (failed requests or steps: {failed})",
          file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return correct
