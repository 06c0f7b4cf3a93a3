"""Finds a cell's files by name, runs its driver on the chip, and prints
the result line.

Nothing here names a configuration, a traffic mix, a model or a metric:
a cell of ``BENCHMARK.json`` names its configuration
(``configs/<config>.json``, whose ``conv`` names the model module
``yardstick/models/<conv>.py``) and its traffic
(``traffic/<traffic>.json``, whose ``driver`` names a module under
``drivers/``); each per-layer metric is read by
``metrics/<metric name>.py``; each cell's limits for ``correct`` are in
``limits/<cell>.json``.
"""

from __future__ import annotations

import contextlib
import functools
import importlib
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
#: JAX's persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: where a configuration's ``conv`` finds its model module
MODELS = HERE / "yardstick" / "models"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


class NoModel(LookupError):
    """A configuration's ``conv`` names no model module."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_files(bench: dict, workload: str, base: pathlib.Path = HERE
               ) -> dict:
    """The cell's entry and its configuration, traffic and limits."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    return {
        "cell": cell,
        "config": load_json(base / "configs" / f"{cell['config']}.json"),
        "traffic": load_json(base / "traffic" / f"{cell['traffic']}.json"),
        "limits": load_json(base / "limits" / f"{workload}.json"),
    }


def metrics_of(bench: dict, workload: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "perfbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_of(conv: str):
    """The model module a configuration's ``conv`` names; raises
    :class:`NoModel`, listing the known models, where there is none."""
    path = MODELS / f"{conv}.py"
    if not path.is_file():
        known = sorted(p.stem for p in MODELS.glob("*.py"))
        raise NoModel(f"no model {conv!r} under {MODELS}; known: {known}")
    return _load_once(path)


@functools.lru_cache(maxsize=None)
def _load_once(path: pathlib.Path):
    """One module object a path, so that its jitted functions keep their
    compiled programs from call to call."""
    return load_module(path)


def driver_of(traffic: dict):
    """The driver module a traffic mix's ``driver`` names."""
    return importlib.import_module(f"perfbench.drivers.{traffic['driver']}")


def read_layer_metrics(metrics: list[dict], ctx: dict,
                       base: pathlib.Path = HERE) -> dict:
    """Each metric's own reader; a reader that finds nothing returns
    ``None`` and the metric is left out."""
    out = {}
    for m in metrics:
        value = load_module(base / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def configure_jax() -> None:
    """Compile cache in the checkout, every program cached.  Runs before
    anything opens a backend."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int) -> dict:
    """The devices JAX opened; raises :class:`NoChip` unless they are
    TPUs, at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r}); "
                     "this benchmark measures only on the chip")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


class Tracer:
    """The traced run's instruments: the program's span recorder and a
    JAX profiler trace of the window, reduced on stop.  Off, every
    method does nothing."""

    def __init__(self, on: bool):
        self.on = on
        self.spans: list[tuple[str, float, float]] = []
        self._dir = None

    def start(self) -> None:
        if not self.on:
            return
        import jax
        from repro.obsv.trace import TRACE
        self._dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        TRACE.clear()
        TRACE.enable()
        jax.profiler.start_trace(self._dir, profiler_options=opts)

    def annotate(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def stop(self, rounds: list[tuple[float, float]]) -> dict | None:
        """Stop, and reduce the trace over the window; program spans
        move onto the profiler's clock by the offset of the harness's
        own ``bench.round`` annotations."""
        if not self.on:
            return None
        import jax
        from repro.obsv.trace import TRACE
        from perfbench.yardstick import trace
        jax.profiler.stop_trace()
        TRACE.disable()
        try:
            events = trace.load(self._dir, host_names={"bench.round"})
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        marks = trace.host_spans(events, "bench.round")
        if len(marks) != len(rounds):
            raise RuntimeError(f"{len(marks)} bench.round marks in the "
                               f"trace for {len(rounds)} rounds")
        offs = sorted(m[0] - r[0] * 1e9 for m, r in zip(marks, rounds))
        off = offs[len(offs) // 2]
        spans = [("round driver: sampling, glue", a * 1e9 + off,
                  b * 1e9 + off) for a, b in rounds]
        for name, _, _, t0, dur, _ in list(TRACE.events):
            self.spans.append((name, t0, dur))
            spans.append((name, t0 * 1e9 + off, (t0 + dur) * 1e9 + off))
        window = (marks[0][0], marks[-1][1])
        return trace.reduce(events, window=window, spans=spans,
                            default_label="between rounds")

    def memory_peak(self) -> int:
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))


def run_cell(args, t_start: float) -> tuple[dict, list]:
    """Everything but the printing: returns the result line's object and
    the timed rounds."""
    bench = load_json(ROOT / "BENCHMARK.json")
    files = cell_files(bench, args.workload)
    configure_jax()
    dev = device_info(int(files["cell"]["chips"]))
    from perfbench.yardstick.peaks import peaks_for
    peaks = peaks_for(dev["kind"])
    sys.path.insert(0, str(ROOT / "src"))
    driver = driver_of(files["traffic"])
    tracer = Tracer(bool(args.trace))
    res = driver.run(files["config"], files["traffic"], seed=args.seed,
                     seconds=float(args.seconds), t_start=t_start,
                     limits=files["limits"], tracer=tracer)
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    if args.trace:
        ctx = {**res["layer_ctx"], "peaks": peaks,
               "config": files["config"]}
        metrics = read_layer_metrics(
            metrics_of(bench, args.workload, "per_layer"), ctx)
    else:
        metrics = {m["name"]: {"value": float(res["end_to_end"][m["name"]]),
                               "unit": units[m["name"]]}
                   for m in metrics_of(bench, args.workload, "end_to_end")}
    device = {**dev, "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    d = res["layer_ctx"].get("device")
    if d is not None:
        device["busy_s"] = d["busy_s"]
        device["window_s"] = d["window_s"]
        out["breakdown"] = {"device_ops": d["device_ops"],
                            "idle_gaps": d["idle_gaps"]}
    out["checks"] = res["checks"]
    return out, res["layer_ctx"].get("rounds", ())


def emit(out: dict, rounds=()) -> None:
    """Each timed round's seconds, then the compared numbers beside their
    limits as the last lines of stderr; then the result as the last line
    of stdout."""
    if rounds:
        print("rounds_s " + " ".join(repr(b - a) for a, b in rounds),
              file=sys.stderr, flush=True)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
