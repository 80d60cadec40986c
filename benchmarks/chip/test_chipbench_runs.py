"""Whole benchmark runs on the CPU at a small size: the harness's look for
a chip is skipped, the rest of a run is driven as on the chip.

A sound run is correct.  The control (the plain reference put in the
program's place, its activations in float8 where the configuration
states bfloat16) and each fault the cells can have (an answer altered
where it is produced, half of each batch left out) come out not
correct."""
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import control  # noqa: E402
import harness  # noqa: E402

OFFLINE = "resnet18-mixed.offline-b32"
SERVER = "resnet18-mixed.server-poisson"


@pytest.fixture(scope="module")
def small_bench(tmp_path_factory):
    """BENCHMARK.json with its ResNet-18 cells on the program's small
    preset (32x32 images, 10 classes), everything else as committed."""
    d = tmp_path_factory.mktemp("chipbench")
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    src = next(c for c in bench["configs"] if c["name"] == "resnet18-mixed")
    cfg = harness.load_json(os.path.join(ROOT, src["file"]))
    cfg_dir = os.path.join(HERE, "configs")
    cfg.update(smoke=True, img_size=32, n_classes=10,
               plan=os.path.join(cfg_dir, cfg["plan"]),
               reference=os.path.join(cfg_dir, cfg["reference"]))
    (d / "small.json").write_text(json.dumps(cfg))
    bench["paths"] = [os.path.relpath(HERE, d)]
    bench["configs"] = [dict(src, file="small.json")]
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["config"] == "resnet18-mixed"]
    # The open-loop traffic and its tails, which no committed cell runs.
    bench["workloads"].append({"name": SERVER, "config": "resnet18-mixed",
                               "traffic": "server-poisson-r18", "chips": 1,
                               "why": "open loop"})
    bench["end_to_end"].append({"name": "latency_p99_ms", "unit": "ms",
                                "better": "lower", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": [SERVER]})
    path = d / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


def run(bench, cell, put=None, seconds=0.5):
    return harness.run(cell, 2 ** 31 + 99, seconds, False, time.time(),
                       platform="cpu", bench_path=bench,
                       system_hook=control.PUT[put] if put else None)


@pytest.mark.parametrize("cell,metric", [(OFFLINE, "images_per_s"),
                                         (SERVER, "latency_p99_ms")])
def test_sound_run_is_correct(small_bench, cell, metric, capsys):
    r = run(small_bench, cell)
    # Set-up warmed every shape: nothing compiles inside the window.
    assert "window: 0 programs compiled or loaded" in capsys.readouterr().err
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) >= {"setup_s", metric}
    assert list(r)[-1] == "checks"
    assert r["checks"]["logit_gap"]["value"] <= \
        r["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("put", ["control", "altered", "half"])
def test_control_and_faults_are_not_correct(small_bench, put):
    r = run(small_bench, OFFLINE, put)
    assert not r["correct"], r["checks"]
    assert r["checks"]["logit_gap"]["value"] > \
        r["checks"]["logit_gap"]["limit"]
