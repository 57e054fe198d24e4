"""Shared GNN building blocks.

Message passing is implemented with `jnp.take` (gather) +
`jax.ops.segment_sum` over an edge-index list — JAX has no sparse
message-passing primitive (BCOO only), so this IS the system's SpMM
layer (see kernel_taxonomy §GNN).  The Pallas `spmm_ell` kernel is the
TPU hot-loop realization of the same contraction for ELL-layout
graphs; these segment-op paths are the XLA reference used by the
models (and the dry-run).
"""

from __future__ import annotations

import contextlib
import contextvars

import jax
import jax.numpy as jnp

from repro.models.common import fan_in_init

# §Perf (dimenet/ogb_products): when set, every segment-reduce output
# is pinned to a sharded layout so GSPMD lowers the cross-device
# combine as a reduce-scatter (1x payload) instead of an all-reduce
# (2x payload) — and downstream edge-sharded consumers stay aligned.
_SEG_SHARDING: contextvars.ContextVar = contextvars.ContextVar(
    "gnn_segment_sharding", default=None
)


@contextlib.contextmanager
def segment_output_sharding(sharding_1d):
    """sharding_1d: a jax NamedSharding whose spec shards axis 0."""
    tok = _SEG_SHARDING.set(sharding_1d)
    try:
        yield
    finally:
        _SEG_SHARDING.reset(tok)


def _constrain_seg(out):
    sh = _SEG_SHARDING.get()
    if sh is None:
        return out
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = P(sh.spec[0], *([None] * (out.ndim - 1)))
    return jax.lax.with_sharding_constraint(
        out, NamedSharding(sh.mesh, spec)
    )


def scatter_sum(values, index, n):
    return _constrain_seg(
        jax.ops.segment_sum(values, index, num_segments=n)
    )


# §Perf H2 iter 2 (dimenet/ogb_products): GSPMD lowers a segment-sum
# over mesh-sharded values as per-device DENSE partials + all-reduce
# (390 GB/device on the ogb triplet aggregation).  When the index list
# is *owner-aligned* — host-sorted so that shard p's values target
# exactly the segment range [p·n/P, (p+1)·n/P), which build_triplets'
# dst-ordered output gives after align_segments() padding — the
# reduction is purely local: a shard_map segment-sum with zero
# collectives.  This is the same owner-aligned exchange discipline the
# AGM engine's 1D partition uses (DESIGN.md §2).
_ALIGNED_TOPO: contextvars.ContextVar = contextvars.ContextVar(
    "gnn_aligned_topology", default=None
)


@contextlib.contextmanager
def aligned_scatter(topo):
    tok = _ALIGNED_TOPO.set(topo)
    try:
        yield
    finally:
        _ALIGNED_TOPO.reset(tok)


def scatter_sum_owner_aligned(values, index, n):
    """segment-sum for an owner-aligned (host-sorted+padded) index
    list; falls back to the plain path outside distributed context or
    when shapes don't divide the mesh."""
    topo = _ALIGNED_TOPO.get()
    P_ = topo.n_devices if topo is not None else 1
    if (topo is None or P_ == 1 or n % P_ != 0
            or values.shape[0] % P_ != 0):
        return scatter_sum(values, index, n)
    from jax.sharding import PartitionSpec as P

    n_loc = n // P_
    axes = topo.all_axes

    def local(v, s):
        # v (T/P, d) local slice; s (T/P,) GLOBAL segment ids, all
        # inside this shard's range by the alignment contract
        rank = 0
        for name in axes:
            rank = rank * topo.mesh.shape[name] + jax.lax.axis_index(
                name
            )
        local_ids = s - rank * n_loc
        return jax.ops.segment_sum(v, local_ids, num_segments=n_loc)

    trail = tuple([None] * (values.ndim - 1))
    out = jax.shard_map(
        local, mesh=topo.mesh,
        in_specs=(P(axes, *trail), P(axes)),
        out_specs=P(axes, *trail),
    )(values, index)
    return out


def scatter_mean(values, index, n, eps: float = 1e-9):
    s = scatter_sum(values, index, n)
    cnt = scatter_sum(jnp.ones(values.shape[:1], values.dtype), index, n)
    return s / jnp.maximum(cnt, eps)[:, None]


def scatter_max(values, index, n):
    return _constrain_seg(
        jax.ops.segment_max(values, index, num_segments=n)
    )


def gather_src(x, edge_src):
    return jnp.take(x, edge_src, axis=0)


def init_mlp(key, dims, dtype=jnp.float32):
    ks = jax.random.split(key, len(dims) - 1)
    return {
        f"w{i}": fan_in_init(ks[i], (dims[i], dims[i + 1]), dims[i], dtype)
        for i in range(len(dims) - 1)
    } | {
        f"b{i}": jnp.zeros((dims[i + 1],), dtype)
        for i in range(len(dims) - 1)
    }


def mlp_apply(p, x, act=jax.nn.silu, final_act: bool = False):
    n = len([k for k in p if k.startswith("w")])
    for i in range(n):
        x = x @ p[f"w{i}"] + p[f"b{i}"]
        if i < n - 1 or final_act:
            x = act(x)
    return x
