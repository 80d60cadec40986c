"""Serving launcher CLI: packed mixed-precision batched generation.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --reduced \
        --w-bits 4 --k 4 --batch 4 --prompt-len 16 --new-tokens 32

Loads (or initializes) QAT params, packs them at the requested
(w_Q, k) point — the paper's "new CNN without a new FPGA image" path —
and runs batched greedy generation with per-phase timing.  On a real
slice the same command serves the full config over the production mesh
(weights sharded by SERVE_RULES; see launch/dryrun.py for the compiled
proof of every cell).

CNN archs serve batched images through ``ImageServer`` instead of the
LM generator.  EVERY arch additionally accepts a layer-wise precision
plan — CNNs per conv layer, LM families per projection (``q``, ``mlp``,
``expert``, ...) or per decoder depth (``l3.mlp``):

    PYTHONPATH=src python -m repro.launch.serve --arch resnet18 --reduced \
        --plan examples/plans/resnet18_mixed.json --batch 8
    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --reduced \
        --plan examples/plans/granite_8b_mixed.json --batch 4

The plan JSON (core/plan.py schema; emitted by the sensitivity-guided
DSE in core/planner.py) assigns each layer its own
(w_bits, k, channel_wise, dataflow); packing + serving resolve the same
per-layer formats through the shared funnel (depth-heterogeneous LM
plans serve via format-grouped scans), so switching plan points is a
re-pack, never a new serve graph implementation.

Multi-device serving (DESIGN.md §8): ``--mesh DxM`` shards the packed
tree and the batch over a (data, model) serve mesh; ``--devices N``
forces N host CPU devices first (XLA placeholder topology — the
laptop-scale stand-in for a real slice), e.g.

    PYTHONPATH=src python -m repro.launch.serve --arch resnet18 \
        --reduced --devices 8 --mesh 8x1 --batch 32

SLO-aware frontier serving (DESIGN.md §9): ``--frontier manifest.json``
packs EVERY plan point in the manifest from one weight store and
serves an overload demo burst through the SLO scheduler — under
deadline pressure (``--slo-ms``) requests degrade to the faster/lower-
bit plan points and drain back when the queue clears:

    PYTHONPATH=src python -m repro.launch.serve --arch resnet18 \
        --reduced --frontier examples/frontiers/resnet18_frontier.json \
        --slo-ms 4000
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import numpy as np

from repro import configs
from repro.checkpoint import CheckpointStore
from repro.core import flags
from repro.core.plan import FrontierManifest, PrecisionPlan
from repro.core.precision import PrecisionPolicy
from repro.launch.mesh import make_serve_mesh, mesh_axes, parse_mesh_spec
from repro.runtime.serve import Generator, ImageServer, pack_for_serving
from repro.runtime.telemetry import (NULL_METRICS, NULL_TRACER,
                                     MetricsRegistry, Tracer,
                                     device_time_split, layer_attribution)


def _mk_telemetry(args):
    """(tracer, metrics) for this run: live objects only when any
    telemetry flag is set — otherwise the shared no-op pair, so an
    untraced serve takes the zero-cost fast path everywhere."""
    if args.trace or args.metrics_dump or args.profile:
        return Tracer(), MetricsRegistry()
    return NULL_TRACER, NULL_METRICS


class _Profiled:
    """Context manager for ``--profile DIR``: a jax.profiler trace of
    the measured section (host+device timelines, open in Perfetto /
    TensorBoard), no-op when the flag is absent."""

    def __init__(self, profile_dir):
        self.dir = profile_dir

    def __enter__(self):
        if self.dir:
            jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, exc_type, exc, tb):
        if self.dir:
            jax.profiler.stop_trace()
            print(f"[serve] jax profiler trace -> {self.dir}")


def _attribution_summary(api, plan_or_policy, measured_s, *, batch=None,
                         tokens=None):
    """Per-layer achieved-vs-roofline utilization against the planner's
    latency model at the resolved per-layer word lengths."""
    if api.family == "cnn":
        gemms = api.mod.gemm_workload(api.cfg, batch=batch or 1)
    else:
        gemms = api.gemm_workload(tokens or 1)
    return layer_attribution(gemms, plan_or_policy, measured_s)


def _print_attribution(rep) -> None:
    if not rep.get("layers"):
        return
    print(f"[serve] roofline: measured {rep['measured_s']*1e3:.2f}ms vs "
          f"model {rep['roofline_s']*1e3:.3f}ms -> "
          f"{100 * rep['roofline_fraction']:.2f}% of roofline "
          f"({rep['achieved_tops']:.3f} achieved / "
          f"{rep['roofline_tops']:.1f} roofline TOps/s, "
          f"peak int8 {rep['peak_int8_tops']:.0f})")
    top = sorted(rep["layers"], key=lambda l: -l["attributed_s"])[:4]
    for l in top:
        print(f"[serve]   {l['name']:<12} w{l['w_bits']}  "
              f"{l['bound']:<7} share {100 * l['share']:5.1f}%  "
              f"achieved {l['achieved_tops']:8.3f} / "
              f"roofline {l['roofline_tops']:6.1f} TOps/s  "
              f"hbm {l['achieved_hbm_gbps']:7.2f} GB/s")


def _export_telemetry(args, tracer, metrics) -> None:
    if args.trace and tracer.enabled:
        tracer.export(args.trace)
        split = device_time_split(tracer)
        print(f"[serve] trace -> {args.trace} "
              f"({len(tracer.events)} events, {tracer.dropped} dropped; "
              f"device calls {split['calls']}: "
              f"dispatch {split['dispatch_s']*1e3:.1f}ms + "
              f"device {split['device_s']*1e3:.1f}ms)")
    if args.metrics_dump and metrics.enabled:
        with open(args.metrics_dump, "w") as f:
            f.write(metrics.prometheus_text())
        print(f"[serve] metrics -> {args.metrics_dump} "
              f"({len(metrics.names())} metrics)")


def _serve_frontier(api, args, mesh) -> int:
    """Pack every manifest plan point from one weight store and push an
    overload burst through the SLO scheduler (DESIGN.md §9)."""
    from repro.runtime.frontier import frontier_from_manifest
    from repro.runtime.slo import SLOScheduler

    manifest = FrontierManifest.load(args.frontier)
    rng = jax.random.PRNGKey(args.seed)
    init_api = configs.get(args.arch, reduced=args.reduced)
    params = init_api.init_params(rng, "train")
    if args.ckpt_dir:
        store = CheckpointStore(args.ckpt_dir)
        _, state = store.restore({"params": params})
        params = state["params"]
        print(f"[serve] restored params from {args.ckpt_dir}")

    t0 = time.perf_counter()
    max_len = args.prompt_len + args.new_tokens
    frontier = frontier_from_manifest(
        api, params, manifest, batch_buckets=(args.batch,),
        max_len=max_len, mesh=mesh)
    print(f"[serve] packed {frontier.n_levels} plan points of {args.arch} "
          f"in {time.perf_counter() - t0:.2f}s: "
          f"{' -> '.join(frontier.names)} (accurate -> fast)")

    data_rng = np.random.default_rng(args.seed)
    if api.family == "cnn":
        mk = lambda: np.asarray(data_rng.normal(
            0.4, 0.5, (api.cfg.img_size, api.cfg.img_size, 3)), np.float32)
    else:
        mk = lambda: (data_rng.integers(
            0, api.cfg.vocab, (args.prompt_len,)).astype(np.int32),
            args.new_tokens)
    for lvl in range(frontier.n_levels):   # warm every level's jit cache
        frontier.serve([frontier.validate(mk())] * args.batch, level=lvl)

    tracer, metrics = _mk_telemetry(args)
    sched = SLOScheduler(frontier, slo_s=args.slo_ms / 1e3,
                         max_queue=max(4 * args.batch * 8, 256),
                         tracer=tracer, metrics=metrics)
    n_req = args.batch * 16                # a burst well past one batch
    t0 = time.perf_counter()
    with _Profiled(args.profile):
        tickets = [sched.submit(mk()) for _ in range(n_req)]
        sched.drain()
        # Post-burst trickle: one request at a time, so the controller
        # sees low pressure and climbs back to the accurate point.
        for _ in range(16):
            tickets.append(sched.submit(mk()))
            sched.drain()
            if sched.level == 0:
                break
    n_req = len(tickets)
    dt = time.perf_counter() - t0
    st = sched.stats()
    by_point = {}
    for t in tickets:
        key = t.plan_point or t.outcome
        by_point[key] = by_point.get(key, 0) + 1
    met = sum(bool(t.deadline_met) for t in tickets)
    print(f"[serve] {n_req} requests in {dt:.2f}s -> {n_req/dt:.1f} req/s "
          f"at slo {args.slo_ms:.0f}ms: {met}/{n_req} deadlines met, "
          f"served by {by_point}")
    print(f"[serve] degraded={st['degraded']:.0f} expired={st['expired']:.0f}"
          f" transitions={st['transitions']:.0f} "
          f"p50={st['p50_latency_s']*1e3:.1f}ms "
          f"p95={st['p95_latency_s']*1e3:.1f}ms "
          f"p99={st['p99_latency_s']*1e3:.1f}ms "
          f"(drained back to level {sched.level}: "
          f"{sched.plan_point})")
    _export_telemetry(args, tracer, metrics)
    return 0


def _serve_cnn(api, policy_or_plan, args, mesh) -> int:
    """Batched image serving of a packed CNN (optionally plan-wise)."""
    mod, cfg = api.mod, api.cfg
    rng = jax.random.PRNGKey(args.seed)
    params = api.init_params(rng, "train")
    state = mod.init_bn_state(mod.specs(cfg))

    t0 = time.perf_counter()
    packed = mod.pack_for_serve(cfg, params, state, policy_or_plan)
    t_pack = time.perf_counter() - t0
    n_bytes = sum(np.asarray(x).nbytes for x in jax.tree.leaves(packed))
    tag = (policy_or_plan.name or "plan"
           if isinstance(policy_or_plan, PrecisionPlan)
           else f"w{policy_or_plan.inner_bits}k{policy_or_plan.k}")
    print(f"[serve] packed {args.arch} [{tag}]: "
          f"{n_bytes/2**20:.1f} MiB in {t_pack:.2f}s")

    plan = (policy_or_plan if isinstance(policy_or_plan, PrecisionPlan)
            else None)
    tracer, metrics = _mk_telemetry(args)
    server = ImageServer(api=api, params=packed, plan=plan,
                         batch_buckets=(args.batch,), mesh=mesh,
                         tracer=tracer, metrics=metrics)
    imgs = np.asarray(
        np.random.default_rng(args.seed).normal(
            0.4, 0.5, (args.batch, cfg.img_size, cfg.img_size, 3)),
        np.float32)
    server.predict(imgs)  # compile
    n0 = len(tracer.events)
    t0 = time.perf_counter()
    with _Profiled(args.profile):
        logits = server.predict(imgs)
    dt = time.perf_counter() - t0
    print(f"[serve] {args.batch} images in {dt:.3f}s -> "
          f"{args.batch/dt:.1f} images/s (img {cfg.img_size}, "
          f"logits {logits.shape})")
    if tracer.enabled:
        split = device_time_split(tracer, since=n0)
        measured = split["device_s"] or dt
        _print_attribution(_attribution_summary(
            api, policy_or_plan, measured, batch=args.batch))
    _export_telemetry(args, tracer, metrics)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True,
                    choices=configs.ARCH_NAMES + configs.RESNET_NAMES)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore QAT params from this trainer checkpoint")
    ap.add_argument("--w-bits", type=int, default=None, choices=(1, 2, 4, 8))
    ap.add_argument("--k", type=int, default=None, choices=(1, 2, 4, 8))
    ap.add_argument("--channel-wise", action="store_true")
    ap.add_argument("--fp-baseline", action="store_true")
    ap.add_argument("--plan", default=None,
                    help="layer-wise precision plan JSON (any arch): "
                         "per-layer w_bits/k/channel_wise/dataflow, "
                         "validated against the arch's layer namespace")
    ap.add_argument("--frontier", default=None,
                    help="frontier manifest JSON (core/plan.py schema): "
                         "pack every plan point from one weight store and "
                         "serve a demo burst through the SLO scheduler")
    ap.add_argument("--slo-ms", type=float, default=4000.0,
                    help="per-request deadline budget for --frontier mode "
                         "(default sized for the CPU-emulation demo; real "
                         "accelerator deployments run ms-scale budgets)")
    ap.add_argument("--spec-decode", type=int, default=None, metavar="K",
                    help="speculative decoding: draft K tokens per cycle "
                         "on a low-bit repack of the SAME checkpoint and "
                         "verify them in one batched forward on the "
                         "serving plan (LM archs; greedy output is "
                         "bit-identical to serving the plan alone)")
    ap.add_argument("--draft-plan", default=None, metavar="PLAN.json",
                    help="precision plan for the --spec-decode draft "
                         "point (e.g. examples/plans/"
                         "granite_8b_draft_w2.json)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=None,
                    help="force N host CPU devices (placeholder topology; "
                         "must run before the first jax computation)")
    ap.add_argument("--mesh", default=None,
                    help="serve mesh 'DATAxMODEL' (e.g. 8x1): shard the "
                         "packed tree + batch across local devices")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="export a Chrome trace_event JSON of the run "
                         "(loadable in Perfetto / chrome://tracing)")
    ap.add_argument("--metrics-dump", default=None, metavar="OUT.prom",
                    help="dump the metrics registry in Prometheus text "
                         "exposition format at exit")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler device trace of the "
                         "serve loop into DIR (TensorBoard-loadable)")
    args = ap.parse_args(argv)

    flags.enable_compile_cache()
    if args.devices:
        # Device count locks at the first backend initialization; jax is
        # imported but nothing has touched devices yet at this point.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()
    mesh = None
    if args.mesh is not None:
        d, m = parse_mesh_spec(args.mesh)
        mesh = make_serve_mesh(d, m)
        print(f"[serve] mesh {dict(mesh_axes(mesh))} over "
              f"{mesh.devices.size} of {len(jax.devices())} devices")

    if args.fp_baseline:
        policy = PrecisionPolicy(quantize=False)
    elif args.w_bits or args.k:
        wb = args.w_bits or 4
        policy = PrecisionPolicy(inner_bits=wb, k=args.k or min(wb, 4),
                                 channel_wise=args.channel_wise)
    else:
        policy = None

    if args.frontier is not None:
        if (args.plan or args.fp_baseline or args.w_bits or args.k
                or args.channel_wise):
            raise SystemExit(
                "--frontier carries its own plan points; it conflicts with "
                "--plan/--w-bits/--k/--channel-wise/--fp-baseline")
        api = configs.get(args.arch, reduced=args.reduced)
        return _serve_frontier(api, args, mesh)

    plan = None
    if args.plan is not None:
        if (args.fp_baseline or args.w_bits or args.k
                or args.channel_wise):
            raise SystemExit(
                "--plan carries the per-layer policy; it conflicts with "
                "--w-bits/--k/--channel-wise/--fp-baseline")
        plan = PrecisionPlan.load(args.plan)
        policy = plan  # the plan IS the api policy, any family

    api = configs.get(args.arch, reduced=args.reduced, policy=policy)
    if plan is not None:
        plan.validate_layers(api.plan_layer_names())
    if args.spec_decode is not None:
        if args.draft_plan is None:
            raise SystemExit("--spec-decode requires --draft-plan")
        if api.family == "cnn" or api.needs_frames:
            raise SystemExit(
                "--spec-decode serves autoregressive LM archs only")
    if api.family == "cnn":
        return _serve_cnn(api, api.policy, args, mesh)

    rng = jax.random.PRNGKey(args.seed)
    # Init/restore always use the uniform single-stack layout: trainer
    # checkpoints are written under the uniform policy, and a
    # depth-scoped plan's grouped specs would not match their leaf
    # paths.  pack_for_serving re-groups the stack to the plan's layout
    # (the train-once / re-pack-any-plan-point flow, DESIGN.md §7.3).
    init_api = (configs.get(args.arch, reduced=args.reduced)
                if plan is not None else api)
    params = init_api.init_params(rng, "train")
    if args.ckpt_dir:
        store = CheckpointStore(args.ckpt_dir)
        _, state = store.restore({"params": params})
        params = state["params"]
        print(f"[serve] restored params from {args.ckpt_dir}")

    tracer, metrics = _mk_telemetry(args)
    if isinstance(api.policy, PrecisionPlan):
        tag = (f"plan [{api.policy.name or args.plan}] w_bits "
               f"{'/'.join(map(str, api.policy.distinct_wbits()))}")
    elif not api.policy.quantize:
        tag = "w_Q=FP"
    else:
        tag = f"w_Q={api.policy.inner_bits} k={api.policy.k}"
    t0 = time.perf_counter()
    if args.spec_decode is not None:
        # One float checkpoint, two packed views: the shipped plan
        # verifies, a uniform low-bit repack drafts (runtime/specdec.py).
        from repro.runtime.specdec import SpeculativeGenerator
        dplan = PrecisionPlan.load(args.draft_plan)
        dplan.validate_layers(api.plan_layer_names())
        gen = SpeculativeGenerator(
            api=api, train_params=params, draft_plan=dplan,
            k=args.spec_decode,
            max_len=args.prompt_len + args.new_tokens, mesh=mesh,
            tracer=tracer, metrics=metrics)
        print(f"[serve] packed {args.arch} at {tag} + draft point "
              f"[{dplan.name or args.draft_plan}] from one weight store "
              f"in {time.perf_counter() - t0:.2f}s "
              f"(spec-decode k={args.spec_decode})")
        frames = None
    else:
        packed = pack_for_serving(api, params, mesh=mesh)
        t_pack = time.perf_counter() - t0
        n_bytes = sum(np.asarray(x).nbytes for x in jax.tree.leaves(packed))
        print(f"[serve] packed {args.arch} at {tag}: "
              f"{n_bytes/2**20:.1f} MiB in {t_pack:.2f}s")
        gen = Generator(api=api, params=packed, mesh=mesh,
                        tracer=tracer, metrics=metrics)
        frames = (np.zeros((args.batch, api.cfg.n_audio, api.cfg.d_model),
                           np.float32) if api.needs_frames else None)
    prompts = np.asarray(
        np.random.default_rng(args.seed).integers(
            0, api.cfg.vocab, (args.batch, args.prompt_len)), np.int32)
    gen_kw = {} if args.spec_decode is not None else {"frames": frames}

    # compile (spec mode needs one full-k cycle to warm the draft scan)
    warm = (2 if args.spec_decode is None
            else min(args.new_tokens, args.spec_decode + 2))
    gen.generate(prompts, warm, **gen_kw)
    if args.spec_decode is not None:
        gen.drafted_tokens = gen.accepted_tokens = 0  # drop warmup stats
    n0 = len(tracer.events)
    t0 = time.perf_counter()
    with _Profiled(args.profile):
        out = gen.generate(prompts, args.new_tokens, **gen_kw)
    dt = time.perf_counter() - t0
    toks = args.batch * args.new_tokens
    print(f"[serve] {toks} tokens in {dt:.2f}s -> {toks/dt:.1f} tok/s "
          f"(batch {args.batch})")
    if args.spec_decode is not None:
        print(f"[serve] specdec accept rate {gen.accept_rate:.3f} "
              f"({gen.accepted_tokens}/{gen.drafted_tokens} drafted tokens "
              f"accepted at k={args.spec_decode})")
    print(f"[serve] sample: {out[0, :12].tolist()}")
    if tracer.enabled:
        split = device_time_split(tracer, since=n0)
        measured = split["device_s"] or dt
        _print_attribution(_attribution_summary(
            api, api.policy, measured,
            tokens=args.batch * (args.prompt_len + args.new_tokens)))
    _export_telemetry(args, tracer, metrics)
    return 0


if __name__ == "__main__":
    sys.exit(main())
