"""Tests of the chip benchmark's harness that need no chip and no model
run: files found by name, the work count, kernel routing, the trace
reduction, traffic generation and the latency tails."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import loadgen  # noqa: E402
import tracereduce  # noqa: E402
import workcount  # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]


def reference():
    return harness.load_module(os.path.join(HERE, "configs",
                                            "resnet_reference.py"))


def config(name):
    cfg = next(c for c in BENCH["configs"] if c["name"] == name)
    return harness.load_json(os.path.join(ROOT, cfg["file"]))


def plan_of(cfg):
    return harness.load_json(os.path.join(HERE, "configs", cfg["plan"]))


@pytest.mark.parametrize("name", CELLS)
def test_cell_finds_config_traffic_and_readers_by_name(name):
    cell = harness.Cell(name)
    assert cell.cfg["name"] == cell.spec["config"]
    assert cell.traffic["loop"] in ("closed", "open")
    names = [m["name"] for m in cell.end_to_end + cell.per_layer]
    assert "setup_s" in names and len(cell.end_to_end) >= 2
    assert len(cell.per_layer) >= 1
    for metric in names:
        assert callable(cell.reader(metric).read)
    assert callable(cell.system_module().System)
    assert callable(cell.reference_module().forward)


@pytest.mark.parametrize("name", ["resnet18-mixed", "resnet50-mixed"])
def test_config_plan_validates_and_matches_program_sizes(name):
    from repro import configs
    from repro.core.plan import validate_plan_json
    cfg = config(name)
    plan = validate_plan_json(os.path.join(HERE, "configs", cfg["plan"]),
                              arch=cfg["model"])
    pcfg = configs.get(cfg["model"], policy=plan).cfg
    assert (pcfg.depth, pcfg.img_size, pcfg.n_classes, pcfg.width) == \
        (cfg["depth"], cfg["img_size"], cfg["n_classes"], cfg["width"])
    assert tuple(pcfg.stages) == tuple(cfg["stages"])


def test_cell_added_as_files_is_picked_up(tmp_path):
    """A new cell, traffic mix and metric reader in a directory of their
    own are found with no edit to the harness."""
    bench = dict(BENCH)
    bench["paths"] = ["bench"]
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    (tmp_path / "bench" / "metrics").mkdir()
    cfg_file = os.path.join(ROOT, BENCH["configs"][0]["file"])
    bench["configs"] = [dict(BENCH["configs"][0], file=cfg_file)]
    cfg_name = bench["configs"][0]["name"]
    bench["workloads"] = [{"name": cfg_name + ".trickle", "config": cfg_name,
                           "traffic": "trickle", "chips": 1, "why": "test"}]
    bench["per_layer"] = BENCH["per_layer"] + [{
        "name": "batch_mean", "unit": "images", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "latency_p99_ms", "workloads": [cfg_name + ".trickle"]}]
    traffic = {"loop": "open", "buckets": [1, 2, 4, 8], "max_wait_s": 0.002,
               "max_queue": 256, "pool": 16, "rate_per_s": 100}
    (tmp_path / "bench" / "traffic" / "trickle.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench" / "metrics" / "batch_mean.py").write_text(
        "def read(run):\n    return 4.0\n")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    cell = harness.Cell(cfg_name + ".trickle", str(path))
    assert cell.traffic["rate_per_s"] == 100
    assert cell.warm_sizes() == [1, 2, 3, 4, 5, 6, 7, 8]
    assert [m["name"] for m in cell.metrics(True)] == ["batch_mean"]
    assert cell.reader("batch_mean").read(None) == 4.0
    # Files the new directory does not hold come from the benchmark's own.
    assert callable(cell.reader("setup_s").read)
    with pytest.raises(harness.BenchError):
        harness.Cell("no-such-cell", str(path))


@pytest.mark.parametrize("arch", ["resnet18", "resnet50"])
def test_work_count_is_twice_the_programs_macs(arch):
    from repro import configs
    from repro.models import resnet
    name = arch + "-mixed"
    lays = reference().layers(config(name))
    pcfg = configs.get(arch).cfg
    gemms = resnet.gemm_workload(pcfg, batch=1)
    assert workcount.ops_per_image(lays) == 2 * sum(g.macs for g in gemms)
    assert sorted((l["name"], l["M"], l["K"], l["N"]) for l in lays) == \
        sorted((g.name, g.m, g.k, g.n) for g in gemms)


def test_layer_work_counts_the_algorithm_not_the_padding():
    lay = {"M": 56 * 56, "K": 576, "N": 64, "h_in": 56, "cin": 64,
           "residual": True}
    w = workcount.layer_work(lay, w_bits=4, batch=32)
    assert w["ops"] == 2 * 32 * 56 * 56 * 576 * 64
    fmap = 32 * 56 * 56 * 64
    assert w["bytes"] == fmap * 1 + fmap * 2 * 2 + 576 * 64 * 4 / 8
    peak = workcount.peaks("TPU v5 lite")
    assert workcount.least_time(w, peak) == max(
        w["ops"] / 393e12, w["bytes"] / 819e9)
    with pytest.raises(KeyError):
        workcount.peaks("cpu")


def test_routing_reads_the_compiled_step():
    """The Mosaic calls of ResNet-18's batch-32 step, as compiled for a
    v5e, go to the layers whose shapes and plane formats they carry."""
    with open(os.path.join(HERE, "testdata",
                           "resnet18_b32_v5e_kernel_calls.txt")) as f:
        calls = workcount.kernel_calls(f.read())
    cfg = config("resnet18-mixed")
    ref = reference()
    lays = ref.layers(cfg)
    fm = {l["name"]: ref.layer_format(plan_of(cfg), l) for l in lays}
    route = workcount.route(calls, lays, fm, batch=32)
    assert len(route) == len(lays) == 21
    conv = sorted(v for k, v in route.items() if k.startswith("conv_mpmm"))
    assert conv == ["s0b0c1", "s0b0c2", "s0b1c1", "s0b1c2", "s1b1c2"]
    assert route["mpmm.16"] == "stem" and route["mpmm.31"] == "fc"
    with pytest.raises(ValueError):
        workcount.route(calls[:-1], lays, fm, batch=32)


class _Run:
    def __init__(self, ops, routing, layers, formats, buckets=(32,)):
        class _Cell:
            traffic = {"buckets": list(buckets)}
        self.cell, self.routing, self.layers = _Cell(), routing, layers
        self.formats = formats
        self.trace = {"ops": ops}
        self.peak = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def test_kernel_roofline_counts_each_call_at_its_layer():
    lay = {"name": "a", "M": 784, "K": 1152, "N": 128, "h_in": 28,
           "cin": 128, "residual": False}
    least = workcount.least_time(workcount.layer_work(lay, 2, 32),
                                 {"int8_ops_per_s": 393e12,
                                  "hbm_bytes_per_s": 819e9})
    ops = [("mpmm.1", 0.0, 2 * least), ("mpmm.1", 1.0, 1.0 + 2 * least),
           ("fusion.3", 2.0, 3.0)]
    run = _Run(ops, {"mpmm.1": "a"}, [lay], {"a": (2, 2)})
    assert workcount.kernel_roofline(run, "mpmm") == pytest.approx(50.0)
    # No call of the kernel in the stretch: no reading, never 0.
    assert workcount.kernel_roofline(run, "conv_mpmm") is None
    # A call the compiled step does not explain: no reading.
    run.trace["ops"].append(("mpmm.9", 4.0, 5.0))
    assert workcount.kernel_roofline(run, "mpmm") is None
    # Several buckets: call names repeat across programs, no reading.
    run = _Run(ops, {"mpmm.1": "a"}, [lay], {"a": (2, 2)}, buckets=(1, 8))
    assert workcount.kernel_roofline(run, "mpmm") is None


def _recorded():
    with open(os.path.join(HERE, "testdata", "recorded_trace.json")) as f:
        return json.load(f)


class _Event:
    def __init__(self, name, start_s, end_s):
        self.name = name
        self.start_ns = round(start_s * 1e9)
        self.duration_ns = round(end_s * 1e9) - self.start_ns


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, [_Event(*e) for e in events]


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def test_trace_reduction_reads_a_recorded_trace():
    """Four steps of ``resnet18-mixed.offline-b32`` traced on a v5e; the
    expected numbers were worked out on a 1 us grid, not by this code."""
    rec = _recorded()
    pd = type("PD", (), {"planes": [
        _Plane("/host:CPU", []),
        _Plane(rec["device_plane"], [_Line("XLA Modules", rec["modules"]),
                                      _Line("XLA Ops", rec["ops"])])]})()
    dev = tracereduce.events_from_profile(pd)[rec["device_plane"]]
    assert all(" = " not in n and not n.startswith("%")
               for n, _, _ in dev["ops"])
    t0, t1, _ = tracereduce.stretch(dev["modules"])
    want = rec["expect"]
    assert (t0, t1) == pytest.approx(want["stretch"], abs=1e-9)
    clipped = tracereduce.clip(dev["ops"], t0, t1)
    busy = tracereduce.busy_seconds(clipped)
    assert busy == pytest.approx(want["busy_s"], abs=2e-6)
    assert 1 - busy / (t1 - t0) == pytest.approx(want["idle_share"],
                                                 abs=1e-4)
    by = tracereduce.time_by(clipped)
    for kernel, seconds in want["kernel_s"].items():
        assert by[kernel] == pytest.approx(seconds, abs=2e-6)
    bd = tracereduce.breakdown(clipped, [tuple(h) for h in rec["host"]],
                               t0, t1)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    name, longest = bd["idle_gaps"][0]
    assert longest == pytest.approx(want["longest_gap_s"], abs=2e-6)
    assert name == want["longest_gap_host"]
    gaps = [g for _, g in bd["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert tracereduce.stretch(dev["modules"][:1]) is None


def test_union_and_gaps_by_hand():
    ev = [("a", 0.0, 1.0), ("b", 0.5, 2.0), ("c", 3.0, 4.0)]
    assert tracereduce.union(ev) == [(0.0, 2.0), (3.0, 4.0)]
    assert tracereduce.busy_seconds(ev) == 3.0
    assert tracereduce.gaps(ev, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0),
                                               (4.0, 5.0)]
    host = [("step", 1.5, 3.5), ("predict", 2.5, 3.4)]
    assert tracereduce.host_activity(host, 2.7) == "predict"
    assert tracereduce.host_activity(host, 2.2) == "step"
    assert tracereduce.host_activity(host, 9.0) == "outside the harness"


def test_seeded_arrivals_and_payloads_repeat_exactly():
    tr = {"rate_per_s": 500.0}
    seed = 2 ** 31 + 12345
    a = loadgen.arrival_times(tr, seed, 10.0)
    assert np.array_equal(a, loadgen.arrival_times(tr, seed, 10.0))
    b = loadgen.arrival_times(tr, seed + 1, 10.0)
    assert len(a) == len(b) == 5000 and not np.array_equal(a, b)
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 10.0
    p = loadgen.payload_order(256, 1000, seed)
    assert np.array_equal(p, loadgen.payload_order(256, 1000, seed))
    assert sorted(p[:256]) == list(range(256))


def test_image_pool_repeats_exactly():
    sysmod = harness.load_module(os.path.join(HERE, "systems",
                                              "image_serving.py"))
    cfg = dict(config("resnet18-mixed"), img_size=16)
    x = sysmod.make_pool(cfg, 2 ** 33 + 7, 8)
    assert x.shape == (8, 16, 16, 3) and x.dtype == np.float32
    assert np.array_equal(x, sysmod.make_pool(cfg, 2 ** 33 + 7, 8))
    assert not np.array_equal(x, sysmod.make_pool(cfg, 2 ** 33 + 8, 8))
    assert len({x[i].tobytes() for i in range(8)}) == 8


def _record(due, admit, done):
    due, admit, done = (np.asarray(v, float) for v in (due, admit, done))
    return loadgen.Record(due=due, submit=due + 0.001, admit=admit, done=done,
                          item=np.zeros(len(due), int),
                          results=[np.zeros(3)] * len(due), refused=0,
                          t_start=0.0, t_end=float(done.max()))


def test_tails_are_over_all_requests_from_their_due_times():
    n = 1000
    due = np.arange(n) * 0.01
    lat = np.full(n, 0.002)
    lat[-12:] = 0.5                      # a stall at the end: 12 late
    done = due + lat
    admit = done - 0.001
    run = harness.Run.__new__(harness.Run)
    run.record = _record(due, admit, done)
    p99 = harness.load_module(os.path.join(HERE, "metrics",
                                           "latency_p99_ms.py")).read(run)
    p50 = harness.load_module(os.path.join(HERE, "metrics",
                                           "latency_p50_ms.py")).read(run)
    assert p99 == pytest.approx(500.0) and p50 == pytest.approx(2.0)
    qw = harness.load_module(os.path.join(HERE, "metrics",
                                          "queue_wait_p99_ms.py")).read(run)
    assert qw == pytest.approx(499.0)
    # Nearest rank: with 10 late in 1000 the p99 is the last on-time one.
    lat[-12:-10] = 0.002
    run.record = _record(due, due + lat - 0.001, due + lat)
    p99 = harness.load_module(os.path.join(HERE, "metrics",
                                           "latency_p99_ms.py")).read(run)
    assert p99 == pytest.approx(2.0)


def test_watch_counts_compiles_gc_passes_and_steps():
    import gc
    import jax
    import jax.numpy as jnp
    with harness.Watch(jax) as watch:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
        gc.collect()
        with watch.annotate(harness._untraced)("step"):
            sum(range(10000))
        with watch.annotate(harness._untraced)("submit"):
            pass
    inside = watch.compiles
    jax.jit(lambda x: x - 5)(jnp.ones(3)).block_until_ready()
    assert watch.compiles == inside >= 1      # only the window counts
    assert len(watch.gc_s) >= 1 and min(watch.gc_s) >= 0
    assert len(watch.steps) == 1 and watch.steps[0][0] > 0
    assert watch.summary().startswith(f"window: {inside} programs compiled")


def test_no_tpu_means_nonzero_exit_and_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
    assert "no tpu" in p.stderr
    # A checkout that holds only the benchmark has no program to run.
    bare = tmp_path / "bare"
    shutil.copytree(HERE, bare / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), bare)
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py"] + cmd[3:],
                       cwd=bare, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
