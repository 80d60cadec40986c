"""Run one cell of ``BENCHMARK.json`` once.

Everything that belongs to one configuration, traffic mix or metric is
found by its name:

* ``BENCHMARK.json`` (at the root of the checkout) names the cell, its
  configuration file and its traffic;
* ``traffic/<traffic>.json`` holds the traffic's parameters (``loadgen``);
* the configuration file names its plan, its plain reference and the
  system module that serves it (``systems/<name>.py``), all beside it or
  under this directory;
* ``metrics/<metric>.py`` reads one metric from a finished run
  (``read(run) -> float | None``; ``None`` leaves it out of the line).

A run: set-up (weights from the seed, pack, every program the window
uses compiled or loaded, the payload pool), the measured window, then,
once the window has closed and the device's peak memory has been read,
the comparison of every answer with the plain reference.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List

import numpy as np

import loadgen
import tracereduce
import workcount

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# The served graph rounds each layer's output to bfloat16 before the next
# layer quantizes it; XLA's default excess precision may skip that
# rounding inside a fusion.  Every run, traced or not, sets this flag, so
# the program timed is the program checked.
XLA_FLAG = "--xla_allow_excess_precision=false"
TRACE_STRETCH_S = 3.0


class BenchError(RuntimeError):
    """A run that cannot give a result: no line is printed."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "chipbench_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Cell:
    """A cell of ``BENCHMARK.json`` with its configuration, traffic and
    metric readers, all found by name.

    Files are looked up under the benchmark's first path beside that
    ``BENCHMARK.json``, then beside this file, so a cell, traffic or
    metric added as files is picked up with no edit here."""

    def __init__(self, name: str, bench_path: str = None):
        bench_path = bench_path or os.path.join(ROOT, "BENCHMARK.json")
        bench = load_json(bench_path)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise BenchError(f"no cell {name!r} in {bench_path}")
        self.name, self.spec = name, cells[name]
        root = os.path.dirname(os.path.abspath(bench_path))
        self.dirs = [os.path.join(root, bench["paths"][0]), HERE]
        cfgs = {c["name"]: c for c in bench["configs"]}
        self.cfg_path = os.path.join(root, cfgs[self.spec["config"]]["file"])
        self.cfg = load_json(self.cfg_path)
        self.cfg_dir = os.path.dirname(self.cfg_path)
        self.traffic = load_json(self.find("traffic", self.spec["traffic"],
                                           ".json"))
        self.end_to_end = [m for m in bench["end_to_end"] if applies(m, name)]
        self.per_layer = [m for m in bench["per_layer"] if applies(m, name)]

    def find(self, kind: str, name: str, ext: str) -> str:
        for d in self.dirs:
            path = os.path.join(d, kind, name + ext)
            if os.path.exists(path):
                return path
        raise BenchError(f"no {kind} file {name + ext} under {self.dirs}")

    @property
    def chips(self) -> int:
        return self.spec["chips"]

    def metrics(self, trace: bool) -> List[Dict]:
        return self.per_layer if trace else self.end_to_end

    def reader(self, metric: str):
        return load_module(self.find("metrics", metric, ".py"))

    def system_module(self):
        return load_module(self.find("systems", self.cfg["system"], ".py"))

    def reference_module(self):
        return load_module(os.path.join(self.cfg_dir, self.cfg["reference"]))

    def warm_sizes(self) -> List[int]:
        """Every batch size the window can dispatch: the closed loop only
        ever sends full batches of the largest bucket; the open loop can
        send any size up to it."""
        top = max(self.traffic["buckets"])
        if self.traffic["loop"] == "closed":
            return [top]
        return list(range(1, top + 1))


class Run:
    """What a finished run hands the metric readers."""

    def __init__(self, cell: Cell, record: loadgen.Record, setup_s: float,
                 device_kind: str):
        self.cell, self.record, self.setup_s = cell, record, setup_s
        self.device_kind = device_kind
        self.layers = None              # the reference's layer list
        self.formats = None             # {layer: (w_bits, k)}
        self.routing = None             # {kernel call name: layer}
        self.predict_spans: List[Dict] = []
        self.trace = None               # see Profile.reduce

    @property
    def peak(self) -> Dict:
        return workcount.peaks(self.device_kind)

    def latencies(self) -> np.ndarray:
        r = self.record
        return (r.done - r.due)[r.ok]


class HostLog:
    """What the host did, as ``(name, start, end)`` on the harness clock:
    the traced run's stand-in for the profiler's host tracer."""

    def __init__(self):
        self.spans: List[tracereduce.Event] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = loadgen.CLOCK()
        try:
            yield
        finally:
            self.spans.append((name, t0, loadgen.CLOCK()))


def _untraced(name: str):
    return contextlib.nullcontext()


# The event JAX records for every program it compiles or loads from the
# compile cache.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_WATCHES: List["Watch"] = []


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    if event == COMPILE_EVENT:
        for w in _WATCHES:
            w.compiles += 1


class Watch:
    """What the host did in the window besides serving: programs compiled
    or loaded (0 once set-up has warmed every shape), the garbage
    collector's passes, and each scheduler step's wall and thread CPU
    time.  A long step whose CPU time is short waited on something that
    is not its own work."""

    def __init__(self, jax):
        self.compiles, self.gc_s, self.steps = 0, [], []
        self._gc_t = 0.0
        if not _WATCHES and not getattr(jax, "_chipbench_listening", False):
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax._chipbench_listening = True

    def __enter__(self):
        _WATCHES.append(self)
        gc.callbacks.append(self._gc)
        return self

    def __exit__(self, *exc):
        _WATCHES.remove(self)
        gc.callbacks.remove(self._gc)

    def _gc(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._gc_t = loadgen.CLOCK()
        else:
            self.gc_s.append(loadgen.CLOCK() - self._gc_t)

    def annotate(self, inner):
        """``inner`` with every ``step`` timed."""
        def ann(name: str):
            return _TimedStep(self, inner(name)) if name == "step" \
                else inner(name)
        return ann

    def summary(self) -> str:
        top = sorted(self.steps, reverse=True)[:3]
        steps = ", ".join(f"{w * 1e3:.1f} ms wall / {c * 1e3:.1f} ms cpu"
                          for w, c in top)
        return (f"window: {self.compiles} programs compiled or loaded; "
                f"{len(self.gc_s)} gc passes, longest "
                f"{max(self.gc_s, default=0.0) * 1e3:.1f} ms; "
                f"{len(self.steps)} steps, "
                f"{sum(w > 0.05 for w, _ in self.steps)} over 50 ms, "
                f"longest {steps}")


class _TimedStep:
    def __init__(self, watch: Watch, inner):
        self.watch, self.inner = watch, inner

    def __enter__(self):
        self.inner.__enter__()
        self.t = (loadgen.CLOCK(), time.thread_time())

    def __exit__(self, *exc):
        w, c = self.t
        self.watch.steps.append((loadgen.CLOCK() - w, time.thread_time() - c))
        return self.inner.__exit__(*exc)


class _Annotated:
    """The server as the scheduler sees it, with ``predict`` logged."""

    def __init__(self, server, annotate):
        self._server, self._annotate = server, annotate
        self.batch_buckets = server.batch_buckets
        self.api = server.api

    def predict(self, images):
        with self._annotate("predict"):
            return self._server.predict(images)


def _prepare_jax(on_chip: bool):
    """Flags and the compile cache, before JAX starts.  Tests on the
    CPU (``on_chip=False``) leave the process's settings alone."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    if not on_chip:
        import jax
        return jax
    flags = os.environ.get("XLA_FLAGS", "")
    if XLA_FLAG not in flags:
        os.environ["XLA_FLAGS"] = (flags + " " + XLA_FLAG).strip()
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def require_devices(jax, chips: int, platform: str = "tpu"):
    devs = jax.devices()
    if devs[0].platform != platform:
        raise BenchError(f"no {platform}: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        t_start: float, platform: str = "tpu", bench_path: str = None,
        system_hook=None) -> Dict:
    """One run of one cell; returns the result line as a dict.

    ``platform`` and ``bench_path`` let the tests run a small cell on the
    CPU; ``system_hook(system)`` may replace parts of the system after
    set-up (the fault tests break the timed path with it)."""
    cell = Cell(cell_name, bench_path)
    jax = _prepare_jax(platform == "tpu")
    devs = require_devices(jax, cell.chips, platform)
    dev = devs[0]
    marks = [("runtime start", time.time())]

    sysmod, ref = cell.system_module(), cell.reference_module()
    tracer = None
    if trace:
        from repro.runtime.telemetry import Tracer
        tracer = Tracer(clock=loadgen.CLOCK, capacity=1 << 20)
    system = sysmod.System(cell.cfg, cell.cfg_dir, ref, seed, cell.traffic,
                           tracer=tracer)
    marks.append(("weights and pack", time.time()))
    system.warm(cell.warm_sizes())
    marks.append(("warm-up", time.time()))
    pool = system.pool(cell.traffic["pool"])
    marks.append(("payloads", time.time()))
    calls = profile = None
    if trace:
        calls = workcount.kernel_calls(
            system.compiled_text(max(cell.traffic["buckets"])))
        profile = Profile(jax, tempfile.mkdtemp(prefix="chipbench-trace-"))
        marks.append(("kernel routing and marker", time.time()))
    if system_hook is not None:
        system_hook(system)
    # What set-up left behind is never garbage: keep the collector's
    # full passes in the window off it.
    gc.collect()
    gc.freeze()
    setup_s = time.time() - t_start
    t = t_start
    for what, at in marks:
        print(f"[chipbench] set-up: {what} {at - t:.3f} s", file=sys.stderr)
        t = at

    from repro.runtime.scheduler import ImageScheduler
    annotate = HostLog() if trace else _untraced
    tr = cell.traffic
    sched = ImageScheduler(_Annotated(system.server, annotate),
                           max_queue=tr["max_queue"],
                           max_wait_s=tr["max_wait_s"], clock=loadgen.CLOCK)
    drive = loadgen.drive_closed if tr["loop"] == "closed" else \
        loadgen.drive_open
    with Watch(jax) as watch:
        record = drive(sched, pool, tr, seed, seconds,
                       watch.annotate(annotate),
                       stretch_hooks=(profile.on, profile.off) if trace
                       else None, stretch_s=TRACE_STRETCH_S)
    print(f"[chipbench] {watch.summary()}", file=sys.stderr)

    stats = dev.memory_stats() or {}
    out = Run(cell, record, setup_s, dev.device_kind)
    out.layers = ref.layers(cell.cfg)
    out.formats = {l["name"]: ref.layer_format(system.plan_json, l)
                   for l in out.layers}
    if trace:
        out.predict_spans = [ev[6] for ev in tracer.events
                             if ev[1] == "predict" and ev[6]
                             and record.t_start <= ev[4] <= record.t_end]
        out.routing = workcount.route(calls, out.layers, out.formats,
                                      max(tr["buckets"]))
        out.trace = profile.reduce(record, annotate)
        shutil.rmtree(profile.log_dir, ignore_errors=True)

    metrics = {}
    for m in cell.metrics(trace):
        v = cell.reader(m["name"]).read(out)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    sched = system.server = None
    system.free()
    checks = check(system, pool, record, cell.cfg["limits"])
    answered = int(record.ok.sum())
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(record.due) + record.refused,
        "failed": record.refused + len(record.due) - answered,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs),
                   "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))},
    }
    if trace:
        result["device"]["busy_s"] = out.trace["busy_s"]
        result["device"]["window_s"] = out.trace["window_s"]
        result["breakdown"] = out.trace["breakdown"]
    result["checks"] = checks
    return result


class Profile:
    """The traced stretch: the profiler on, with the host tracer off, and
    the stretch bounded on the device by two runs of a marker program
    (compiled here, at set-up)."""

    def __init__(self, jax, log_dir: str):
        import jax.numpy as jnp

        def chipbench_mark(x):
            return x + 1
        self.jax, self.log_dir = jax, log_dir
        self.mark = jax.jit(chipbench_mark)
        self.x = jnp.zeros((8, 128), jnp.float32)
        self.mark(self.x).block_until_ready()
        self.dispatched = None

    def on(self) -> None:
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        self.jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.dispatched = loadgen.CLOCK()
        self.mark(self.x).block_until_ready()

    def off(self) -> None:
        self.mark(self.x).block_until_ready()
        self.jax.profiler.stop_trace()

    def reduce(self, record: loadgen.Record, host: HostLog) -> Dict:
        """Busy time and op time on each chip over the stretch, the
        breakdown, and the images answered in it."""
        import glob

        paths = glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise BenchError("the profiler wrote no trace")
        devices = tracereduce.events_from_profile(
            self.jax.profiler.ProfileData.from_file(paths[0]))
        first = sorted(devices)[0] if devices else None
        span = tracereduce.stretch(devices[first]["modules"]) if first \
            else None
        if span is None:
            raise BenchError("the trace holds no marked stretch")
        t0, t1, mark = span
        busy = [tracereduce.busy_seconds(tracereduce.clip(d["ops"], t0, t1))
                for d in devices.values()]
        if not max(busy) > 0:
            raise BenchError("no operation ran on the device in the stretch")
        # Host log onto the device clock: the first marker started right
        # after the host dispatched it.
        shift = mark - self.dispatched
        log = [(n, a + shift, b + shift) for n, a, b in host.spans]
        ops = devices[first]["ops"]
        h0, h1 = record.stretch
        done = record.ok & (record.done >= h0) & (record.done <= h1)
        return {"busy_s": float(np.mean(busy)), "window_s": t1 - t0,
                "ops": [e for e in ops if e[1] >= t0 and e[2] <= t1],
                "host_s": h1 - h0, "images": int(done.sum()),
                "breakdown": tracereduce.breakdown(
                    tracereduce.clip(ops, t0, t1), log, t0, t1)}


def check(system, pool: np.ndarray, record: loadgen.Record,
          limits: Dict) -> Dict[str, Dict]:
    """Every answer of the window against the plain reference of its
    image: the widest logit gap, in units of the reference logits'
    spread over classes for that image; answers that hold a value that
    is not finite; and requests left unanswered."""
    ref = system.reference_logits(pool).astype(np.float32)
    scale = ref.std(axis=1, keepdims=True)
    done = [(i, r) for i, r in zip(record.item, record.results)
            if r is not None]
    gap, nonfinite = 0.0, 0
    for k in range(0, len(done), 4096):
        items = np.array([i for i, _ in done[k:k + 4096]])
        got = np.stack([r for _, r in done[k:k + 4096]]).astype(np.float32)
        finite = np.isfinite(got).all(axis=1)
        nonfinite += int((~finite).sum())
        if finite.any():
            d = np.abs(got[finite] - ref[items[finite]]) / scale[items[finite]]
            gap = max(gap, float(np.max(d)))
    return {"logit_gap": {"value": gap, "limit": limits["logit_gap"]},
            "nonfinite_answers": {"value": nonfinite, "limit": 0},
            "unanswered": {"value": int((~record.ok).sum()), "limit": 0}}
