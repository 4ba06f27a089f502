"""Bucketed, hierarchical, optionally-compressed gradient synchronisation —
the paper's communication phase as a first-class runtime feature.

The paper shows that Horovod's transport leaves a 100 Gbps NIC at <32 Gbps
and that a *well-scheduled* communication phase (fusion buffers + full link
utilization) reaches a ~100 % scaling factor.  On TPU the transport is
XLA-driven, so the levers that remain at our layer are exactly the ones
this module implements:

- **fusion buckets** (paper: 64 MB / 5 ms): gradients are flattened and
  packed into <=``fusion_buffer_mb`` slabs so each collective moves a
  large contiguous buffer instead of per-tensor messages (the per-tensor
  negotiation overhead is the reason measured Horovod *degrades* with
  tensor count — §2.2);
- **hierarchical all-reduce**: reduce-scatter inside the pod over ICI,
  all-reduce across pods over the (slower) DCN on the 1/N-sized shard,
  all-gather inside the pod — wire-optimal for 2-level topologies;
- **gradient compression** (paper §3.2): fp16 / int8 / ternary / top-k via
  the Pallas kernels in ``repro.kernels``, applied per bucket.  Quantized
  buckets are exchanged with all-gather + local fused reduction (Horovod
  compression semantics: sums are computed on dequantized values, so
  compression error does not accumulate across hops).

Everything runs inside ``jax.shard_map`` with explicit ``jax.lax``
collectives.  ``sync_grads`` is the one entry point: ``launch/train.py``
calls it inside the same map that computes each device's loss and
gradients when ``CommConfig.mode == "explicit"``, so the bucketed exchange
is the step's only gradient reduction.  ``mode == "auto"`` leaves gradient
averaging to XLA SPMD, the measured baseline.
"""
from __future__ import annotations

from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import CommConfig
from repro.kernels import ops as kops


# ---------------------------------------------------------------------------
# bucketing: pytree <-> fixed-size flat slabs
# ---------------------------------------------------------------------------

class BucketPlan:
    """Static packing plan: leaf -> (bucket id, offset) assignments.

    Built once per param-tree structure (shapes are static under jit).
    Leaves are packed in pytree order — the order backward produces them —
    mirroring the paper's fusion buffer fill order.
    """

    def __init__(self, shapes: Sequence[Tuple[int, ...]], dtypes,
                 limit_bytes: int):
        self.shapes = list(shapes)
        self.sizes = [int(np.prod(s)) if s else 1 for s in shapes]
        self.dtypes = list(dtypes)
        self.assignments: List[Tuple[int, int]] = []      # (bucket, offset)
        self.bucket_sizes: List[int] = []
        self.bucket_tensors: List[int] = []               # leaves per bucket
        cur, cur_bytes, cur_tensors = 0, 0, 0
        offset = 0
        for size, dtype in zip(self.sizes, self.dtypes):
            nbytes = size * jnp.dtype(dtype).itemsize
            if cur_bytes > 0 and cur_bytes + nbytes > limit_bytes:
                self.bucket_sizes.append(offset)
                self.bucket_tensors.append(cur_tensors)
                cur += 1
                cur_bytes, offset, cur_tensors = 0, 0, 0
            self.assignments.append((cur, offset))
            offset += size
            cur_bytes += nbytes
            cur_tensors += 1
        if offset:
            self.bucket_sizes.append(offset)
            self.bucket_tensors.append(cur_tensors)

    @property
    def n_buckets(self) -> int:
        return len(self.bucket_sizes)

    def comm_plan(self, comm: CommConfig):
        """Lower this packing into the shared comm-schedule IR.

        Buckets are packed (and flushed) in pytree order — the backward
        production order — so the plan's ``bucket_order()`` is exactly what
        the simulator predicts for the same scheduler: the runtime executes
        its collectives in that order (simulator <-> runtime parity).
        Packed buckets are f32, hence 4 bytes per element.
        """
        from repro.core.schedule import lower_buckets
        return lower_buckets(
            [(0.0, float(n_elems * 4), n_tensors)
             for n_elems, n_tensors in zip(self.bucket_sizes,
                                           self.bucket_tensors)],
            scheduler=comm.scheduler, n_chunks=comm.sched_chunks)


def make_plan(tree: Any, limit_mb: float) -> Tuple[BucketPlan, Any]:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    plan = BucketPlan([l.shape for l in leaves], [l.dtype for l in leaves],
                      int(limit_mb * 1024 * 1024))
    return plan, treedef


def pack(plan: BucketPlan, leaves: Sequence[jnp.ndarray]) -> List[jnp.ndarray]:
    """Leaves -> list of flat f32 buckets."""
    parts: List[List[jnp.ndarray]] = [[] for _ in range(plan.n_buckets)]
    for leaf, (b, _) in zip(leaves, plan.assignments):
        parts[b].append(leaf.astype(jnp.float32).reshape(-1))
    return [jnp.concatenate(p) for p in parts]


def unpack(plan: BucketPlan, buckets: Sequence[jnp.ndarray]) -> List[jnp.ndarray]:
    out = []
    for (b, off), size, shape, dtype in zip(plan.assignments, plan.sizes,
                                            plan.shapes, plan.dtypes):
        out.append(jax.lax.dynamic_slice(buckets[b], (off,), (size,))
                   .reshape(shape).astype(dtype))
    return out


# ---------------------------------------------------------------------------
# per-bucket collectives (run inside shard_map)
# ---------------------------------------------------------------------------

def _allreduce_mean(x: jnp.ndarray, axes) -> jnp.ndarray:
    return jax.lax.pmean(x, axes)


def _hierarchical_mean(x: jnp.ndarray, ici_axis: str,
                       dcn_axis: str | None) -> jnp.ndarray:
    """In-pod reduce-scatter -> cross-pod all-reduce -> in-pod all-gather."""
    nd = jax.lax.axis_size(ici_axis)
    pad = (-x.shape[0]) % nd
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    shard = jax.lax.psum_scatter(x.reshape(nd, -1), ici_axis,
                                 scatter_dimension=0, tiled=False)
    if dcn_axis is not None:
        shard = jax.lax.psum(shard, dcn_axis)
    full = jax.lax.all_gather(shard, ici_axis, axis=0, tiled=False)
    full = full.reshape(-1)
    if pad:
        full = full[:-pad]
    n_total = nd * (jax.lax.axis_size(dcn_axis) if dcn_axis else 1)
    return full / n_total


def _compressed_mean(x: jnp.ndarray, comm: CommConfig, axes) -> jnp.ndarray:
    """Horovod-compression semantics: all-gather compressed payloads, then
    one fused dequantize+reduce locally (Pallas ``fused_add``)."""
    n_total = jax.lax.axis_size(axes)
    if comm.compression == "fp16":
        g = jax.lax.all_gather(x.astype(jnp.bfloat16), axes, axis=0,
                               tiled=False)
        g = g.reshape(n_total, -1)
        return kops.fused_add(g) / n_total
    if comm.compression in ("int8", "ternary"):
        enc = (kops.quantize_int8 if comm.compression == "int8"
               else kops.ternarize)
        q, s, n = enc(x)
        qg = jax.lax.all_gather(q, axes, axis=0, tiled=False)
        sg = jax.lax.all_gather(s, axes, axis=0, tiled=False)
        qg = qg.reshape(n_total, *q.shape)
        sg = sg.reshape(n_total, *s.shape)
        deq = jax.vmap(lambda qq, ss: qq.astype(jnp.float32) * ss)(qg, sg)
        total = kops.fused_add(deq.reshape(n_total, -1))
        return total.reshape(q.shape).reshape(-1)[:n] / n_total
    if comm.compression == "topk":
        sparse = kops.topk_sparsify(x, comm.topk_ratio, sample=1 << 14)
        g = jax.lax.all_gather(sparse, axes, axis=0, tiled=False)
        return kops.fused_add(g.reshape(n_total, -1)) / n_total
    raise ValueError(comm.compression)


def _sync_bucket(x: jnp.ndarray, comm: CommConfig,
                 axes: Tuple[str, ...]) -> jnp.ndarray:
    if comm.compression != "none":
        return _compressed_mean(x, comm, axes)
    if comm.hierarchical and len(axes) == 2:
        # axes = (pod, data): ICI inside the pod (data), DCN across (pod)
        return _hierarchical_mean(x, ici_axis=axes[1], dcn_axis=axes[0])
    if comm.hierarchical:
        return _hierarchical_mean(x, ici_axis=axes[0], dcn_axis=None)
    return _allreduce_mean(x, axes)


def exchange_collectives(comm: CommConfig, n_buckets: int) -> int:
    """Payload collectives ``sync_grads`` issues for ``n_buckets`` buckets
    over one mesh axis: what a compiled explicit step must hold, and
    nothing more.  Kinds are not fixed (a TPU compile lowers the
    reduce-scatter, and the int8 scales' all-gather, as all-reduces), so
    this is a count."""
    if comm.compression in ("int8", "ternary"):
        return 2 * n_buckets                # payload + scales all-gathers
    if comm.compression != "none" or not comm.hierarchical:
        return n_buckets                    # one all-gather / all-reduce
    return 2 * n_buckets                    # reduce-scatter + all-gather


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def sync_grads(grads: Any, comm: CommConfig,
               axes: Tuple[str, ...] = ("data",)) -> Any:
    """Average each device's ``grads`` over the mesh axes ``axes``.

    Call inside a ``jax.shard_map`` over ``axes`` (replicated parameters,
    per-device gradients).  Equivalent to
    ``jax.tree.map(lambda g: pmean(g, axes), grads)`` but bucketed (fusion
    buffers), hierarchical, and optionally compressed — the paper's
    communication phase, implemented the way the what-if analysis says it
    should be.
    """
    plan, treedef = make_plan(grads, comm.fusion_buffer_mb)
    buckets = pack(plan, jax.tree_util.tree_leaves(grads))

    # the comm-schedule IR orders the collectives: the same CommPlan the
    # simulator executes, so the runtime issues its buckets in the order the
    # analytic layer predicted (fifo keeps pack order; priority front-loads
    # the model's first layers).  Emission order alone would let XLA's
    # latency-hiding scheduler reorder independent collectives, so each
    # bucket's input is barrier-chained to the previous bucket's output —
    # one collective in flight, in plan order, matching the engine's
    # serialization semantics.
    synced: List[jnp.ndarray] = [None] * len(buckets)  # type: ignore[list-item]
    prev = None
    for b in plan.comm_plan(comm).bucket_order():
        x = buckets[b]
        if prev is not None:
            x, _ = jax.lax.optimization_barrier((x, prev))
        prev = synced[b] = _sync_bucket(x, comm, axes)
    return jax.tree_util.tree_unflatten(treedef, unpack(plan, synced))


def grad_sync_flops_and_bytes(total_bytes: int, n_workers: int,
                              comm: CommConfig) -> dict:
    """Analytic wire traffic of one sync — feeds the simulator/benchmarks."""
    ratio = {"none": 1.0, "fp16": 2.0, "int8": 4.0, "ternary": 4.0,
             "topk": 1.0 / max(comm.topk_ratio, 1e-9) / 2.0}[comm.compression]
    if comm.compression == "none":
        wire = 2.0 * total_bytes * (n_workers - 1) / n_workers
    else:  # all-gather of compressed payloads
        wire = total_bytes / ratio * (n_workers - 1)
    return {"wire_bytes_per_worker": wire, "compression_ratio": ratio}
