"""Batched serving runtime: packed-weight deployment, greedy generation
(LM families) and bucketed image serving (CNN family).

The deployment path is the paper's: take QAT-trained params, pack every
inner linear into k-bit digit planes (nn/quantized.pack_tree), then run
prefill + decode entirely against packed weights through the mpmm path.
Changing w_Q (layer-wise) or gamma_w per channel requires only re-packing
— no recompilation of the serving step (the "no new FPGA image" claim).

Layer-wise ``PrecisionPlan``s are honored by EVERY model family, not
just CNNs: the spec markers carry each layer's workload name, so
``pack_for_serving`` packs every layer at its own (w_bits, k) and both
``Generator`` (LM prefill/decode, format-grouped scans) and
``ImageServer`` (CNN batched forward) serve the same per-layer formats.

Multi-device serving: pass ``mesh=`` (``launch.mesh.make_serve_mesh``)
to ``pack_for_serving`` / ``ImageServer`` / ``Generator`` and the packed
tree is PLACED across the mesh — inner packed digit planes by
``SERVE_RULES`` (tensor-shard over 'model' where a rule names it,
replicated on a pure data-parallel mesh), boundary/embedding layers and
the tiny folded-BN pairs replicated — while the batch axis shards over
'data'.  The step functions are jitted with explicit in/out shardings,
so batched CNN forward and LM prefill/decode run data-parallel.  Batch
entries never mix, so sharded serving is bit-identical to the
single-device path (tests/test_sharded_serve.py proves it for mixed
w8/w4/w2 plans); with ``mesh=None`` nothing changes at all.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch import steps as steps_lib
from repro.nn import param as nnp
from repro.nn import partitioning as part
from repro.nn import quantized as Q
from repro.nn.layers import pack_embed
from repro.runtime.telemetry import as_metrics, as_tracer, device_timed

__all__ = ["pack_for_serving", "serve_shardings", "Generator", "ImageServer"]


def serve_shardings(api, mesh: Mesh):
    """NamedSharding tree for this api's packed serve tree (SERVE_RULES).

    LM families carry logical axes on every serve-spec leaf, so the
    rules place each packed plane (replicated on a (N, 1) data-parallel
    mesh; 'mlp_packed'/'heads_packed' tensor-shard over 'model' when the
    mesh has one).  CNN packed trees (folded-BN tuples, per-layer plane
    formats) replicate wholesale — packed planes are w_Q/8 the int8
    bytes, the paper's whole point, so every device holds the full net.
    """
    if api.family == "cnn":
        return part.replicated(mesh)
    return part.tree_shardings(api.param_axes("serve"), mesh,
                               part.SERVE_RULES)


def pack_for_serving(api, train_params, mesh: Optional[Mesh] = None):
    """Trained QAT tree -> packed serve tree matching specs('serve').

    Works for ANY api.policy — uniform or a layer-wise plan: families
    with format-grouped scans (transformer) first re-layout a
    uniform-trained stack into the plan's groups (``regroup_layers``,
    a pure slicing re-pack), then the marker-named funnel packs every
    layer at its own resolved format.

    With ``mesh=`` the packed tree is placed across the mesh through
    ``serve_shardings`` (digit planes by SERVE_RULES, boundary/embedding
    replicated) so the serve step functions find their weights already
    distributed.
    """
    regroup = getattr(api.mod, "regroup_layers", None)
    if regroup is not None:
        train_params = regroup(api.cfg, train_params, api.policy)
    tspecs = api.specs("train")
    packed = Q.pack_tree(train_params, tspecs, api.policy)
    # embeddings: boundary-class PTQ to int8 codes + step size
    if "embed" in packed and api.policy.quantize and "table" in packed["embed"]:
        packed["embed"] = pack_embed(packed["embed"], api.policy)
    if mesh is not None:
        packed = jax.device_put(packed, serve_shardings(api, mesh))
    return packed


def _is_sds(x) -> bool:
    return isinstance(x, jax.ShapeDtypeStruct)


def _pad_batch(arr: np.ndarray, to: int) -> np.ndarray:
    """Pad the leading axis up to ``to`` by repeating the last row (the
    padded rows' outputs are discarded; batch entries never mix)."""
    if arr.shape[0] == to:
        return arr
    reps = np.repeat(arr[-1:], to - arr.shape[0], axis=0)
    return np.concatenate([arr, reps])


@dataclasses.dataclass
class ImageServer:
    """Batched CNN serving over a packed ``serve_forward`` tree.

    The LM ``Generator`` below is prefill/decode-shaped; CNNs serve one
    stateless forward per request batch.  Incoming batches of any size
    are chunked to the largest bucket and the remainder padded up to the
    smallest bucket that fits, so the jit cache holds exactly
    ``len(batch_buckets)`` compiled graphs regardless of traffic —
    resizing a fleet never pays a recompile.

    ``params`` is a ``models.resnet.pack_for_serve`` tree (or any CNN
    family module exposing ``serve_forward``).

    ``plan`` (a ``core.plan.PrecisionPlan``) overrides the api's uniform
    policy with a layer-wise one — ``params`` must then be packed under
    the same plan.  Serving a different plan point is a re-pack plus a
    new ``ImageServer``; the model and kernel code never change.

    ``mesh`` (``launch.mesh.make_serve_mesh``) turns every bucket graph
    data-parallel: weights replicate across the mesh, the image batch
    shards over 'data' with explicit jit in/out shardings, and each
    bucket is rounded up to a multiple of the data-axis size so every
    device gets an equal shard.  Logits are bit-identical to the
    ``mesh=None`` path — batch entries never mix.
    """

    api: Any
    params: Any
    batch_buckets: tuple = (1, 2, 4, 8)
    impl: str = "auto"
    dataflow: str = "auto"
    plan: Any = None
    mesh: Optional[Mesh] = None
    tracer: Any = None   # telemetry.Tracer; None = the no-op fast path
    metrics: Any = None  # telemetry.MetricsRegistry; None = no-op

    def __post_init__(self):
        if self.api.family != "cnn":
            raise ValueError(f"ImageServer serves CNNs, got family "
                             f"{self.api.family!r}")
        if self.mesh is not None:
            n_data = self.mesh.shape.get("data", 1)
            self.batch_buckets = tuple(
                -(-b // n_data) * n_data for b in self.batch_buckets)
            self.params = jax.device_put(self.params,
                                         part.replicated(self.mesh))
        self.batch_buckets = tuple(sorted(set(self.batch_buckets)))
        self._fns: Dict[int, Any] = {}
        # Traced chunks in progress, counted across the caller's threads.
        self._lock = threading.Lock()
        self._chunks_in_progress = 0
        self.tracer = as_tracer(self.tracer)
        self.metrics = as_metrics(self.metrics)
        self._m_device = self.metrics.histogram("repro_device_time_seconds")

    def _fn(self, bucket: int):
        """One jitted serve graph per batch bucket."""
        if bucket not in self._fns:
            mod, cfg = self.api.mod, self.api.cfg
            pol = self.plan if self.plan is not None else self.api.policy
            fn = lambda p, im: mod.serve_forward(
                cfg, p, im, pol, impl=self.impl, dataflow=self.dataflow)
            if self.mesh is None:
                self._fns[bucket] = jax.jit(fn)
            else:
                # XLA cannot partition a Mosaic kernel, so every device
                # runs the whole graph on its own batch shard (images
                # never mix, so this is the data-parallel program).
                # check_vma=False: pallas_call outputs carry no
                # varying-axes annotation.
                rep = part.replicated(self.mesh)
                dsh = NamedSharding(self.mesh, P("data"))
                self._fns[bucket] = jax.jit(
                    jax.shard_map(fn, mesh=self.mesh,
                                  in_specs=(P(), P("data")),
                                  out_specs=P("data"), check_vma=False),
                    in_shardings=(rep, dsh), out_shardings=dsh)
        return self._fns[bucket]

    def _bucket_for(self, n: int) -> int:
        for b in self.batch_buckets:
            if b >= n:
                return b
        return self.batch_buckets[-1]

    def predict(self, images: np.ndarray) -> np.ndarray:
        """(N, H, W, 3) float images -> (N, n_classes) logits."""
        n = images.shape[0]
        if n == 0:  # a drained request queue is not an error
            return np.zeros((0, self.api.cfg.n_classes), np.float32)
        tr = self.tracer
        outs: List[np.ndarray] = []
        i = 0
        while i < n:
            bucket = self._bucket_for(n - i)
            take = min(n - i, bucket)
            chunk = images[i:i + take]
            if tr.enabled:
                outs.append(self._predict_traced(tr, chunk, bucket))
            else:
                y = self._launch(self._put(chunk, bucket))
                outs.append(self._fetch(y, take))
            i += take
        return np.concatenate(outs)

    def _put(self, chunk: np.ndarray, bucket: int) -> jax.Array:
        """Pad the chunk up to its bucket and start its copy to the
        device (the copy runs on after this returns)."""
        chunk = np.asarray(chunk)
        if len(chunk) < bucket:
            pad = np.zeros((bucket - len(chunk),) + chunk.shape[1:],
                           chunk.dtype)
            chunk = np.concatenate([chunk, pad])
        return jnp.asarray(chunk)

    def _launch(self, x: jax.Array) -> jax.Array:
        return self._fn(x.shape[0])(self.params, x)

    @staticmethod
    def _fetch(y: jax.Array, take: int) -> np.ndarray:
        return np.asarray(y[:take])

    def _predict_traced(self, tr, chunk: np.ndarray, bucket: int
                        ) -> np.ndarray:
        """One chunk with its phases timed: an outer ``predict`` span
        (``cat="device"``; ``dispatch_s`` = issue until the jitted call
        returns, ``device_s`` = from there until the output is ready)
        tiled by ``cat="host"`` children: ``put`` (pad and copy call),
        ``launch`` (the jitted call), ``h2d_wait`` (until the input has
        landed on the device; the step is already queued, so the device
        sees no extra sync), ``device_wait`` (until the output is ready)
        and ``fetch`` (output slice and copy to the host).  The outer
        span's args also carry ``h2d_wait_s``, ``fetch_s`` and ``n``
        (the chunk's images), so a reader of the ``predict`` spans alone
        sees the phases and can tell whether any span went missing.
        ``overlap`` counts the chunks of other ``predict`` calls of this
        server in progress when this chunk's ``put`` began (a scheduler
        that keeps two batches in flight calls from two threads).
        Waiting changes when the host blocks, never the values."""
        with self._lock:
            overlap = self._chunks_in_progress
            self._chunks_in_progress += 1
        try:
            t0 = tr.clock()
            x = self._put(chunk, bucket)
            t_put = tr.clock()
            y = self._launch(x)
            t1 = tr.clock()
            x.block_until_ready()
            t_h2d = tr.clock()
            y.block_until_ready()
            t2 = tr.clock()
            out = self._fetch(y, len(chunk))
            t3 = tr.clock()
        finally:
            with self._lock:
                self._chunks_in_progress -= 1
        tr.span_at("predict", t0, t3, cat="device",
                   args={"bucket": bucket, "n": len(chunk),
                         "dispatch_s": t1 - t0, "device_s": t2 - t1,
                         "h2d_wait_s": t_h2d - t1, "fetch_s": t3 - t2,
                         "overlap": overlap})
        tr.span_at("put", t0, t_put, cat="host", args={"bytes": x.nbytes})
        tr.span_at("launch", t_put, t1, cat="host")
        tr.span_at("h2d_wait", t1, t_h2d, cat="host")
        tr.span_at("device_wait", t_h2d, t2, cat="host")
        tr.span_at("fetch", t2, t3, cat="host")
        self._m_device.observe(t2 - t0, phase="predict")
        return out

    @property
    def compiled_buckets(self) -> tuple:
        return tuple(sorted(self._fns))


@dataclasses.dataclass
class Generator:
    """Greedy batched generator over the uniform model API.

    ``plan`` (a ``core.plan.PrecisionPlan``) overrides the api's uniform
    policy with a layer-wise one, exactly like ``ImageServer.plan`` —
    ``params`` must then be packed under the same plan.  Serving a
    different plan point is a re-pack plus a new ``Generator``; the
    model and kernel code never change.

    ``mesh`` makes prefill and decode data-parallel: ``params`` place by
    SERVE_RULES (``pack_for_serving(mesh=...)`` already did this; the
    jit in_shardings pin it), tokens and the decode cache shard their
    batch axis over 'data' (cache kv_seq additionally over 'model' when
    the mesh has one), and the token batch pads up to a multiple of the
    data-axis size.  Outputs are bit-identical to ``mesh=None``.

    ``sample_fn(logits (B, V), key) -> tokens (B,)`` swaps the greedy
    head for an injectable sampler; ``generate(..., key=...)`` seeds it
    (default ``PRNGKey(0)``) and splits one subkey per emitted token, so
    sampled runs replay exactly.  The DEFAULT (``sample_fn=None``) stays
    pure ``jnp.argmax`` with no key material touched — greedy decode is
    deterministic and bit-exact, the guarantee every packed-vs-qdq and
    speculative-decode identity test in this repo is built on.
    """

    api: Any
    params: Any
    max_len: int = 64
    mode: str = "serve"
    plan: Any = None
    mesh: Optional[Mesh] = None
    tracer: Any = None   # telemetry.Tracer; None = the no-op fast path
    metrics: Any = None  # telemetry.MetricsRegistry; None = no-op
    sample_fn: Any = None  # None = greedy argmax (bit-exact default)

    def __post_init__(self):
        if self.plan is not None:
            self.api = dataclasses.replace(self.api, policy=self.plan)
        self.tracer = as_tracer(self.tracer)
        self.metrics = as_metrics(self.metrics)
        prefill_fn = steps_lib.make_prefill_fn(self.api, mode=self.mode)
        decode_fn = steps_lib.make_decode_fn(self.api, mode=self.mode)
        if self.mesh is None:
            self._cache_sh = None
            self._tok_sh = None
            self._prefill = jax.jit(prefill_fn)
            self._decode = jax.jit(decode_fn)
            self._instrument_steps()
            return
        # Explicit-sharding jits, mirroring launch/dryrun._lower_step:
        # params by SERVE_RULES, batch over 'data', decode cache by
        # cache_axes (batch over 'data', kv_seq over 'model').
        mesh, rules = self.mesh, part.SERVE_RULES
        p_sh = serve_shardings(self.api, mesh)
        tok_sh = part.sharding_for(("batch", "seq"), mesh, rules)
        self._tok_sh = tok_sh
        batch_sh = {"tokens": tok_sh}
        if self.api.needs_frames:
            batch_sh["frames"] = part.sharding_for(
                ("batch", "frames", "act_embed"), mesh, rules)
        try:
            cache_sh = part.tree_shardings(self.api.cache_axes(), mesh, rules)
            # jit in_shardings errors lazily at the first call — check the
            # tree structure against cache_specs NOW so mismatched
            # families fall back instead of exploding mid-generate.
            specs = self.api.cache_specs(2, 8)
            if jax.tree.structure(specs, is_leaf=_is_sds) != \
                    jax.tree.structure(cache_sh):
                raise ValueError("cache_axes does not match cache layout")
            self._cache_sh = cache_sh
            self._decode = jax.jit(
                decode_fn,
                in_shardings=(p_sh, self._cache_sh, tok_sh, None),
                out_shardings=(None, self._cache_sh))
        except Exception:
            # families whose decode cache tree differs from cache_axes
            # (or has none): fall back to sharding propagation.
            self._cache_sh = None
            self._decode = jax.jit(decode_fn)
        self._prefill = jax.jit(prefill_fn, in_shardings=(p_sh, batch_sh))
        self._instrument_steps()

    def _instrument_steps(self) -> None:
        """Wrap the jitted prefill/decode with host/device timing when a
        live tracer is attached — ``device_timed`` returns the original
        callables untouched on the no-op tracer, so the disabled path
        is byte-for-byte the old one.  ``GenerateScheduler`` calls
        ``gen._prefill``/``gen._decode`` directly, so continuous-
        batching steps inherit the spans with no scheduler changes."""
        hist = self.metrics.histogram("repro_device_time_seconds")
        self._prefill = device_timed(self.tracer, "prefill", self._prefill,
                                     hist)
        self._decode = device_timed(self.tracer, "decode", self._decode,
                                    hist)

    def _sample(self, logits: jax.Array, key) -> jax.Array:
        """(B, V) logits -> (B,) token ids through the sampling seam."""
        if self.sample_fn is None:
            return jnp.argmax(logits, -1)  # greedy: deterministic, keyless
        return self.sample_fn(logits, key)

    def generate(self, tokens: np.ndarray, n_new: int,
                 frames: Optional[np.ndarray] = None,
                 key=None) -> np.ndarray:
        b, s = tokens.shape
        if self.sample_fn is not None and key is None:
            key = jax.random.PRNGKey(0)
        n_data = self.mesh.shape.get("data", 1) if self.mesh is not None else 1
        gb = -(-b // n_data) * n_data  # pad batch to an even device split
        tokens = _pad_batch(np.asarray(tokens), gb)
        batch = {"tokens": jnp.asarray(tokens)}
        if self.api.needs_frames:
            frames = (np.asarray(frames) if frames is not None else
                      np.zeros((b, self.api.cfg.n_audio,
                                self.api.cfg.d_model), np.float32))
            batch["frames"] = jnp.asarray(_pad_batch(frames, gb))
        logits, pre_cache = self._prefill(self.params, batch)
        # kv_seq shards over 'model' (SERVE_RULES): round the cache
        # length up to an even split; the tail is never attended
        # (decode masks by `length`), so results are unchanged.
        n_model = (self.mesh.shape.get("model", 1)
                   if self.mesh is not None else 1)
        max_len = -(-(s + n_new) // n_model) * n_model
        cache = self._grow_cache(pre_cache, gb, s, max_len)
        if self._cache_sh is not None:
            cache = jax.device_put(cache, self._cache_sh)
        step_key = None
        if self.sample_fn is not None:
            key, step_key = jax.random.split(key)
        tok = self._sample(logits, step_key)[:, None]
        out = [np.asarray(tok[:, 0])]
        length = jnp.asarray(s, jnp.int32)
        for i in range(n_new - 1):
            if self._tok_sh is not None:
                # argmax output sharding follows the (possibly
                # vocab-sharded) logits; re-pin it to the batch spec the
                # decode jit was compiled for.
                tok = jax.device_put(tok, self._tok_sh)
            logits, cache = self._decode(self.params, cache, tok, length + i)
            if self.sample_fn is not None:
                key, step_key = jax.random.split(key)
            tok = self._sample(logits, step_key)[:, None]
            out.append(np.asarray(tok[:, 0]))
        return np.stack(out, axis=1)[:b]

    def _grow_cache(self, pre_cache, b, s, max_len):
        """Copy prefill caches into decode-sized buffers (family-aware)."""
        specs = self.api.cache_specs(b, max_len)

        def embed(buf_spec, pre):
            buf = jnp.zeros(buf_spec.shape, buf_spec.dtype)
            if pre.shape == buf.shape:
                return pre.astype(buf.dtype)
            # seq axis is the one that differs; left-align the prefix.
            idx = [slice(0, d) for d in pre.shape]
            return buf.at[tuple(idx)].set(pre.astype(buf.dtype))

        family = self.api.family
        if family in ("ssm",):
            return pre_cache  # constant-size state already
        if family == "hybrid":
            # recurrentgemma: re-pack last `window` keys into ring buffers
            return self._rg_cache(pre_cache, b, s, specs)
        return jax.tree.map(embed, specs, pre_cache,
                            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    def _rg_cache(self, pre_cache, b, s, specs):
        states, rem = pre_cache
        st1, st2, kv = states
        w = specs["k"].shape[2]
        k_full, v_full = kv
        take = min(s, w)
        k_ring = jnp.zeros(specs["k"].shape, specs["k"].dtype)
        v_ring = jnp.zeros(specs["v"].shape, specs["v"].dtype)
        # absolute position p lands in slot p % w
        pos = np.arange(s - take, s)
        slots = pos % w
        k_ring = k_ring.at[:, :, slots].set(
            k_full[:, :, s - take:s].astype(k_ring.dtype))
        v_ring = v_ring.at[:, :, slots].set(
            v_full[:, :, s - take:s].astype(v_ring.dtype))
        return {"r1": st1, "r2": st2, "k": k_ring, "v": v_ring,
                "rem": [jax.tree.map(lambda a: a[None], r) for r in rem]}
