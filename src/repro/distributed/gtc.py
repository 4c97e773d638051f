"""Gradient Threshold Compression (paper §2/§3.5; Strom, Interspeech 2015).

The paper's 16-GPU trainer for labeled CE + sMBR.  Strom's algorithm, kept
bit-faithful on the *algorithm* side:

  r      <- r + g                      (error-feedback residual)
  send   <- tau * sign(r) * [|r| > tau]   (1-bit-quantized sparse message)
  r      <- r - send
  update <- sum_over_workers(send)

TPU adaptation (DESIGN.md §2): the GPU implementation ships sparse
(index, ±tau) pairs peer-to-peer; TPU ICI collectives have no sparse
all-reduce, so the transport is a dense psum of the (mostly-zero,
1.58-bit-entropy) send tensor — int8-packed, which is where the
bandwidth saving appears in the collective roofline term.  A psum of
ternary int8 messages over <= 127 workers cannot overflow int8, so the
wire stays 1 byte/element (4x under f32); beyond 127 workers the
accumulation must widen to int32 (``GTCConfig.int32_accum``) and
``pack_int8`` *refuses* to build the narrow wire rather than silently
wrapping.

One code path owns the math.  ``compress_tree`` is the error-feedback
selection (the fused Pallas kernel ``repro.kernels.gtc_compress`` on
TPU, the bitwise-identical pure-jnp ref elsewhere;
``GTCConfig.use_kernel`` overrides that ``kernels._dispatch`` default);
``pack_int8`` / ``unpack_int8`` are the only pack/unpack pair;
``wire_pack`` / ``wire_unpack`` are the wire itself.  Their one program
caller is ``make_sharded_gtc_train_step``, the worker-axis-sharded step
that ``train.GTCShardMap`` wraps (W=1 on a one-device mesh is the
single-worker trainer).  ``simulate_gtc_round`` is the tests'
independent reference of one exchange.

Adaptive threshold: Strom fixes tau; we also provide the common variant
that adapts tau per-tensor to hit a target sparsity, used when sweeping.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.kernels._dispatch import auto_use_kernel
from repro.kernels.gtc_compress import gtc_compress
from repro.kernels.gtc_compress.ref import gtc_compress_ref

tmap = jax.tree_util.tree_map

MAX_INT8_WORKERS = 127       # |sum of W ternary messages| <= W must fit int8


@dataclass(frozen=True)
class GTCConfig:
    tau: float = 1e-3
    quantize_int8: bool = True       # pack the send tensor to int8 on the wire
    n_workers: int = 16
    int32_accum: bool = False        # widen the psum to int32 (required
                                     # beyond 127 workers; the narrow int8
                                     # wire is exact below that)
    use_kernel: Optional[bool] = None  # fused Pallas compression kernel;
                                       # None: on TPU only (_dispatch)


# ----------------------------------------------------------- compression

def compress_leaf(g, r, tau: float, *, use_kernel: Optional[bool] = None):
    """One tensor: error-feedback threshold compression.

    Returns (send, new_residual); send has values in {-tau, 0, +tau}.
    ``use_kernel`` routes through the fused Pallas pass
    (``repro.kernels.gtc_compress`` — same math, one HBM round-trip)
    instead of the pure-jnp reference; ``None`` follows
    ``kernels._dispatch``: the kernel on TPU, the reference elsewhere.
    Both are float32 and bitwise-identical.
    """
    if auto_use_kernel(use_kernel):
        return gtc_compress(g, r, tau)    # auto: compiled on TPU,
                                          # interpret mode elsewhere
    return gtc_compress_ref(jnp.asarray(g), jnp.asarray(r, jnp.float32), tau)


def compress_tree(grads, residuals, tau: float, *,
                  use_kernel: Optional[bool] = None):
    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_r = treedef.flatten_up_to(residuals)
    sends, ress = [], []
    for g, r in zip(flat_g, flat_r):
        s, nr = compress_leaf(g, r, tau, use_kernel=use_kernel)
        sends.append(s)
        ress.append(nr)
    return treedef.unflatten(sends), treedef.unflatten(ress)


# ------------------------------------------------------------------ wire

def pack_int8(send, tau: float, *, n_workers: int = 1,
              int32_accum: bool = False):
    """{-tau,0,tau} -> int8 {-1,0,1}: the wire format (4x smaller than
    f32, 2x smaller than bf16).

    ``n_workers`` is the number of ternary messages the reduction will
    sum.  At int8 accumulation width the sum is exact only while
    ``n_workers <= 127``; past that the packed wire would silently wrap,
    so this *raises* unless the caller opted into int32 accumulation.
    """
    if n_workers > MAX_INT8_WORKERS and not int32_accum:
        raise ValueError(
            f"pack_int8: summing {n_workers} ternary int8 messages "
            f"overflows int8 (|sum| <= {n_workers} > {MAX_INT8_WORKERS}); "
            f"set int32_accum=True to widen the accumulation")
    return jnp.clip(jnp.round(send / tau), -1, 1).astype(jnp.int8)


def unpack_int8(packed, tau: float, n_workers_summed: int = 1):
    """Packed (possibly summed) wire integers -> the averaged float
    update: ``packed * tau / n_workers_summed``.  With
    ``n_workers_summed=1`` this is the exact inverse of ``pack_int8``
    on a single message."""
    out = packed.astype(jnp.float32) * tau
    if n_workers_summed != 1:
        out = out / n_workers_summed
    return out


def wire_pack(send, cfg: GTCConfig):
    """One worker's send tensor -> its wire message: ternary int8 (or
    int32-widened when ``cfg.int32_accum``), or the raw f32 send when
    the wire is unquantized.  Messages from co-resident workers add
    exactly (integers) before the psum."""
    if not cfg.quantize_int8:
        return send
    p = pack_int8(send, cfg.tau, n_workers=cfg.n_workers,
                  int32_accum=cfg.int32_accum)
    return p.astype(jnp.int32) if cfg.int32_accum else p

def wire_unpack(acc, cfg: GTCConfig, *, axis_name: Optional[str] = None):
    """Accumulated wire messages -> the update averaged over
    ``cfg.n_workers`` (the paper applies the raw sum; the average keeps
    the lr independent of the worker count); ``axis_name`` adds the
    cross-device psum (THE collective — at int8 width when quantized and
    not widened)."""
    if axis_name is not None:
        acc = jax.lax.psum(acc, axis_name)
    if cfg.quantize_int8:
        return unpack_int8(acc, cfg.tau, n_workers_summed=cfg.n_workers)
    return acc / cfg.n_workers if cfg.n_workers != 1 else acc


def wire_bytes_per_update(params, cfg: GTCConfig) -> int:
    """Bytes one worker ships per update under ``cfg``'s wire format
    (the collective roofline term the int8 pack is buying down).

    Measured from what ``wire_pack`` — the function the trainer
    actually ships through — emits for each leaf (via eval_shape, no
    compute), so a regression in the packing path moves this number
    rather than leaving an analytic constant standing."""
    total = 0
    for p in jax.tree_util.tree_leaves(params):
        msg = jax.eval_shape(
            lambda s: wire_pack(s, cfg),
            jax.ShapeDtypeStruct(p.shape, jnp.float32))
        total += math.prod(msg.shape) * msg.dtype.itemsize
    return total


def gtc_init(params, cfg: GTCConfig):
    """Error-feedback residuals, per worker: stacked on a leading W dim,
    even at W=1 (each worker carries its own compression error — the
    state ``make_sharded_gtc_train_step`` shards over the worker axis)."""
    return {"residual": tmap(
        lambda p: jnp.zeros((cfg.n_workers,) + p.shape, jnp.float32),
        params)}


# ------------------------------------------------------ shard_map wrapper

def make_sharded_gtc_train_step(loss_fn: Callable,
                                optimizer_update: Callable,
                                cfg: GTCConfig, mesh,
                                worker_axes=("data",),
                                grad_transform: Optional[Callable] = None):
    """GTC with the worker dim sharded over `worker_axes` of `mesh`.

    Batches and error-feedback residuals carry a leading W dim sharded
    over the mesh (each shard unrolls its local worker slice),
    params/opt state are replicated (synchronous SGD: every worker
    applies the same averaged update), and the exchange is
    ``wire_pack`` per worker, a local-W integer sum, then
    ``wire_unpack`` — one psum per leaf, int8-packed.  At one worker the
    wire is a pack/unpack round-trip, bitwise identity on ternary sends.

    loss_fn(params, batch[, rng]) -> (loss, metrics); a loss declaring
    ``rng`` receives a per-(update, worker) folded key — folded OUTSIDE
    the shard_map with the *global* worker index (crossing as raw key
    data), so device count never changes the streams.
    ``grad_transform(grads) -> (grads, extra_metrics)`` runs per worker
    before compression (gradient clipping lives here).  Returns
    step(params, opt_state, gtc_state, batches, lr, rng=None) with lr
    traced — one compile per loss kind.
    """
    from jax.sharding import PartitionSpec as P

    from repro.utils.introspect import takes_rng as _takes

    ax = worker_axes if len(worker_axes) > 1 else worker_axes[0]
    takes_rng = _takes(loss_fn)

    def shard_body(residuals, batches, params, opt_state, lr, wkd):
        def local_one(residual, batch, kd):
            if kd is not None:
                (_, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, batch, rng=jax.random.wrap_key_data(kd))
            else:
                (_, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, batch)
            m = dict(m)
            if grad_transform is not None:
                g, extra = grad_transform(g)
                m.update(extra)
            send, new_res = compress_tree(g, residual, cfg.tau,
                                          use_kernel=cfg.use_kernel)
            return tmap(lambda s: wire_pack(s, cfg), send), new_res, m

        # the local worker slice is unrolled, not vmapped: each worker's
        # compute lowers exactly as a lone worker's does (that is what
        # makes the simulate_gtc_round comparisons *bitwise*, not
        # approximate), and in production the slice is one worker per
        # device anyway
        local_w = jax.tree_util.tree_leaves(residuals)[0].shape[0]
        acc, res_i, ms_i = None, [], []
        for i in range(local_w):
            packed, new_res, m = local_one(
                tmap(lambda r: r[i], residuals),
                tmap(lambda b: b[i], batches),
                None if wkd is None else wkd[i])
            acc = packed if acc is None else tmap(jnp.add, acc, packed)
            res_i.append(new_res)
            ms_i.append(m)
        update = tmap(lambda a: wire_unpack(a, cfg, axis_name=ax), acc)
        new_res = tmap(lambda *xs: jnp.stack(xs), *res_i)
        ms = tmap(lambda *xs: jnp.stack(xs), *ms_i)
        ms["gtc_density"] = jnp.broadcast_to(density(update, cfg.tau),
                                             (local_w,))
        params, opt_state = optimizer_update(params, update, opt_state,
                                             lr=lr)
        return params, opt_state, new_res, ms

    wspec = P(ax)       # leading worker dim sharded
    rspec = P()         # params / opt state / lr replicated

    def step(params, opt_state, gtc_state, batches, lr, rng=None):
        lr = jnp.asarray(lr, jnp.float32)
        if rng is None or not takes_rng:
            fn = jax.shard_map(
                lambda r, b, p, o, l: shard_body(r, b, p, o, l, None),
                mesh=mesh,
                in_specs=(wspec, wspec, rspec, rspec, rspec),
                out_specs=(rspec, rspec, wspec, wspec),
                check_vma=False)
            params, opt_state, res, ms = fn(gtc_state["residual"], batches,
                                            params, opt_state, lr)
        else:
            # per-worker keys folded OUTSIDE shard_map with the global
            # worker index (as the BMUF path does): device count never
            # changes the streams, and raw key data crosses the boundary
            wkd = jax.vmap(lambda i: jax.random.key_data(
                jax.random.fold_in(rng, i)))(jnp.arange(cfg.n_workers))
            fn = jax.shard_map(
                shard_body, mesh=mesh,
                in_specs=(wspec, wspec, rspec, rspec, rspec, wspec),
                out_specs=(rspec, rspec, wspec, wspec),
                check_vma=False)
            params, opt_state, res, ms = fn(gtc_state["residual"], batches,
                                            params, opt_state, lr, wkd)
        return params, opt_state, {"residual": res}, ms

    return step


def density(update_tree, tau: float) -> jnp.ndarray:
    """Fraction of nonzero elements actually shipped (diagnostic)."""
    nz = sum(jnp.sum(jnp.abs(u) > 0).astype(jnp.float32)
             for u in jax.tree_util.tree_leaves(update_tree))
    n = sum(u.size for u in jax.tree_util.tree_leaves(update_tree))
    return nz / max(n, 1)


def adaptive_tau(g, target_density: float):
    """Per-tensor tau that keeps ~target_density of elements (quantile)."""
    q = jnp.quantile(jnp.abs(g.astype(jnp.float32)).reshape(-1),
                     1.0 - target_density)
    return jnp.maximum(q, 1e-12)


# ------------------------------------------------- reference (single host)

def simulate_gtc_round(grads_per_worker, residuals_per_worker, tau: float,
                       *, quantize_int8: bool = False,
                       int32_accum: bool = False):
    """Numpy-free reference of one full ring exchange for tests: returns
    (applied_update, new_residuals).  grads/residuals: lists per worker.

    ``quantize_int8`` reproduces the packed wire exactly as
    ``make_sharded_gtc_train_step`` ships it: each worker's send packed to ternary int8,
    summed at integer width (int8 unless ``int32_accum``), unpacked and
    averaged — integer sums are exact, so the sharded trainer must match
    this bitwise.
    """
    n = len(grads_per_worker)
    sends = []
    new_res = []
    for g, r in zip(grads_per_worker, residuals_per_worker):
        s, nr = compress_tree(g, r, tau, use_kernel=False)
        sends.append(s)
        new_res.append(nr)
    if quantize_int8:
        packed = [tmap(lambda s: pack_int8(s, tau, n_workers=n,
                                           int32_accum=int32_accum), sd)
                  for sd in sends]
        if int32_accum:
            packed = [tmap(lambda p: p.astype(jnp.int32), pk)
                      for pk in packed]
        summed = packed[0]
        for pk in packed[1:]:
            summed = tmap(jnp.add, summed, pk)
        avg = tmap(lambda p: unpack_int8(p, tau, n_workers_summed=n),
                   summed)
        return avg, new_res
    summed = sends[0]
    for s in sends[1:]:
        summed = tmap(jnp.add, summed, s)
    avg = tmap(lambda x: x / n, summed)
    return avg, new_res
