"""Smoke run of packed mixed-precision ResNet-18 serving on one TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the 4x1 data-parallel serve mesh

One process, no children.  Builds ResNet-18 at its full config (224x224,
1000 classes) with random weights from ``--seed``, packs it under the
shipped mixed plan ``examples/plans/resnet18_mixed.json`` (w8k4 / w4k4 /
w2k2), and serves it through the entry points a user calls:

  * ``ImageServer`` with the Pallas ``mpmm`` / ``conv_mpmm`` kernels
    compiled by Mosaic (checked: the lowered graph holds Mosaic custom
    calls), a few batches of 8, and the same packed weights served with
    ``impl="xla"`` on the same chip as the reference — logits must be
    bit-identical;
  * ``ImageScheduler`` in front of that server: a few single-image
    requests, each answer equal to the server's own for that image.

With ``--chips 4`` it serves the same batch over ``make_serve_mesh(4, 1)``
and requires the logits to equal the one-device run; no other phase.

Fails (non-zero exit, no result line) when JAX finds no TPU or any phase
fails.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The images/s printed is a smoke reading, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
# The served graph rounds every layer's output to bf16 before the next
# layer quantizes it.  A Mosaic kernel's output is that bf16 array; XLA,
# by default, may keep the f32 value across a bf16 round trip inside a
# fusion ("excess precision"), which makes the impl="xla" reference
# quantize different values.  Bit-identity needs both to round where
# the program says.  Set before JAX starts; appended, never replaced.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_allow_excess_precision=false").strip()

import numpy as np  # noqa: E402

from repro.core import flags  # noqa: E402

PLAN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "examples", "plans", "resnet18_mixed.json")
BATCH = 8


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(cond, msg: str) -> None:
    """A check that holds under ``python -O`` too (unlike ``assert``)."""
    if not cond:
        raise RuntimeError(msg)


def build(reduced: bool = False, seed: int = 0):
    """(api, plan, packed tree) for ResNet-18 under the mixed plan."""
    import jax

    from repro import configs
    from repro.core.plan import PrecisionPlan

    plan = PrecisionPlan.load(PLAN)
    api = configs.get("resnet18", reduced=reduced, policy=plan)
    mod, cfg = api.mod, api.cfg
    params = api.init_params(jax.random.PRNGKey(seed), "train")
    state = mod.init_bn_state(mod.specs(cfg))
    return api, plan, mod.pack_for_serve(cfg, params, state, plan)


def images(cfg, n: int, seed: int) -> np.ndarray:
    return np.asarray(np.random.default_rng(seed).normal(
        0.4, 0.5, (n, cfg.img_size, cfg.img_size, 3)), np.float32)


def mosaic_calls(server, batch: np.ndarray) -> int:
    """Mosaic kernels in the server's lowered bucket graph (0 = none)."""
    fn = server._fn(server._bucket_for(batch.shape[0]))
    return fn.lower(server.params, batch).as_text().count("tpu_custom_call")


def timed_predict(server, batch: np.ndarray):
    t0 = time.perf_counter()
    y = server.predict(batch)
    return y, time.perf_counter() - t0


def check_logits(y: np.ndarray, n_classes: int) -> None:
    require(y.shape == (BATCH, n_classes), f"logits shape {y.shape}")
    require(np.isfinite(y).all(), "non-finite logits")
    require(float(np.std(y)) > 0.0, "constant logits: top-1 is vacuous")


def serve_phase(api, plan, packed, *, impl: str, n_batches: int, seed: int):
    """ImageServer at ``impl`` against the same weights at impl='xla'.

    Returns the ``impl`` server (warm) and the first batch's logits.
    """
    from repro.runtime.serve import ImageServer

    cfg = api.cfg
    batches = [images(cfg, BATCH, seed + i) for i in range(n_batches)]
    server = ImageServer(api=api, params=packed, plan=plan,
                         batch_buckets=(BATCH,), impl=impl)
    ref = ImageServer(api=api, params=packed, plan=plan,
                      batch_buckets=(BATCH,), impl="xla")
    if impl != "xla":
        n_mosaic = mosaic_calls(server, batches[0])
        log(f"impl={impl}: {n_mosaic} Mosaic kernel calls in the graph")
        require(n_mosaic > 0, "no Mosaic kernel in the served graph")
    y0, t_first = timed_predict(server, batches[0])
    r0, t_ref = timed_predict(ref, batches[0])
    log(f"first call (compile included): impl={impl} {t_first:.3f}s, "
        f"impl=xla {t_ref:.3f}s")
    for i, b in enumerate(batches):
        y = y0 if i == 0 else server.predict(b)
        r = r0 if i == 0 else ref.predict(b)
        check_logits(y, cfg.n_classes)
        n_diff = int(np.sum(y != r))
        log(f"batch {i}: logits vs impl=xla: {n_diff} of {y.size} differ, "
            f"max |diff| {float(np.max(np.abs(y - r))):.3e}; top-1 "
            f"{y.argmax(-1).tolist()}")
        require(n_diff == 0, "pallas logits differ from impl=xla")
        require((y.argmax(-1) == r.argmax(-1)).all(), "top-1 differs")
    return server, y0


def scheduler_phase(server, *, n_requests: int, seed: int) -> None:
    """Single-image requests through ImageScheduler, each answer equal to
    the server's own for that image."""
    from repro.runtime.scheduler import ImageScheduler

    imgs = images(server.api.cfg, n_requests, seed + 1000)
    sched = ImageScheduler(server, max_wait_s=0.0)
    tickets = [sched.submit(im) for im in imgs]
    sched.drain()
    direct = np.concatenate([server.predict(imgs[i:i + BATCH])
                             for i in range(0, n_requests, BATCH)])
    for t, want in zip(tickets, direct):
        require(t.result is not None, f"ticket {t.id} unanswered")
        require(np.array_equal(t.result, want), f"ticket {t.id} differs")
    log(f"scheduler: {n_requests} requests answered in "
        f"{len(sched.dispatched_batches)} batches, all equal to predict")


def throughput(server, seed: int, n: int = 10) -> float:
    """Steady images/s through ``predict`` (warm; smoke reading only)."""
    batch = images(server.api.cfg, BATCH, seed)
    server.predict(batch)
    t0 = time.perf_counter()
    for _ in range(n):
        server.predict(batch)
    return n * BATCH / (time.perf_counter() - t0)


def mesh_phase(api, plan, packed, *, seed: int) -> None:
    """The same batch over a 4x1 serve mesh vs one device."""
    from repro.launch.mesh import make_serve_mesh
    from repro.runtime.serve import ImageServer

    batch = images(api.cfg, BATCH, seed)
    one = ImageServer(api=api, params=packed, plan=plan,
                      batch_buckets=(BATCH,))
    meshed = ImageServer(api=api, params=packed, plan=plan,
                         batch_buckets=(BATCH,), mesh=make_serve_mesh(4, 1))
    require(mosaic_calls(meshed, batch) > 0,
            "no Mosaic kernel in the mesh graph")
    y1, t1 = timed_predict(one, batch)
    y4, t4 = timed_predict(meshed, batch)
    log(f"first call (compile included): 1 device {t1:.3f}s, "
        f"4x1 mesh {t4:.3f}s")
    check_logits(y4, api.cfg.n_classes)
    n_diff = int(np.sum(y1 != y4))
    log(f"4x1 mesh vs 1 device: {n_diff} of {y1.size} logits differ")
    require(n_diff == 0, "mesh logits differ from the one-device run")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batches", type=int, default=3)
    args = ap.parse_args(argv)

    log(f"compile cache: {flags.enable_compile_cache()}")
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX found {dev.platform} devices",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"[chip_smoke] --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 1
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}")

    t0 = time.perf_counter()
    api, plan, packed = build(seed=args.seed)
    log(f"built + packed {api.cfg.name} ({api.cfg.img_size}px, "
        f"{api.cfg.n_classes} classes) under {plan.name} in "
        f"{time.perf_counter() - t0:.2f}s")

    if args.chips == 4:
        mesh_phase(api, plan, packed, seed=args.seed)
    else:
        server, _ = serve_phase(api, plan, packed, impl="auto",
                                n_batches=args.batches, seed=args.seed)
        scheduler_phase(server, n_requests=2 * BATCH - 3, seed=args.seed)
        ips = throughput(server, args.seed)
        log(f"steady {ips:.1f} images/s at batch {BATCH} "
            f"(smoke reading, not a benchmark)")
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            log(f"peak_bytes_in_use {stats['peak_bytes_in_use']}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
