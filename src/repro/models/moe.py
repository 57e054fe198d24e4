"""Expert-parallel Mixture-of-Experts FFN (token-choice top-k).

Design (DESIGN.md §6): experts are sharded over the tensor-parallel
axis (EP-as-TP).  Activations arriving at the FFN are replicated over
`tp` (the Megatron pattern), so every tp shard sees the full local
token set, selects the tokens routed to *its* experts with a local
sort-based dispatch (static capacity C per expert, drops beyond C),
runs its expert FFNs, and the per-shard partial outputs are combined
with the same `psum` a dense TP FFN needs — no all-to-all, no
(N, E, C) one-hot dispatch tensor.  Expert weights are additionally
FSDP-sharded over the dp axes and all-gathered per use (ZeRO-3; the
gather's transpose is a reduce-scatter on the gradient path).

The router is computed identically on every tp shard (same replicated
inputs → same top-k), which keeps dispatch decisions consistent
without any routing collective.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import Topology, swiglu


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_model: int
    d_ff: int
    capacity_factor: float = 1.25
    min_capacity: int = 4
    aux_loss_weight: float = 0.01


def capacity(cfg: MoEConfig, n_local_tokens: int) -> int:
    c = int(cfg.capacity_factor * n_local_tokens * cfg.top_k
            / cfg.n_experts)
    return max(cfg.min_capacity, c)


def _moe_local(x, router_w, w_gate, w_up, w_down, *, cfg: MoEConfig,
               topo: Topology, C: int, fsdp_axes: tuple, dp_axes: tuple):
    """Per-device MoE FFN.  x: (N, d) local tokens (replicated over tp).
    w_*: (E_loc, d/fsdp, f) FSDP-sharded expert weights.  ``dp_axes``
    are the axes the *tokens* are sharded over (may be () when the
    batch is replicated); ``fsdp_axes`` shard the weights regardless."""
    N, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    tp = topo.tp_axis if topo.tp_size > 1 else None
    E_loc = E // (topo.tp_size if tp else 1)

    # FSDP: gather full expert weights for this shard's experts
    if fsdp_axes:
        w_gate = jax.lax.all_gather(w_gate, fsdp_axes, axis=1, tiled=True)
        w_up = jax.lax.all_gather(w_up, fsdp_axes, axis=1, tiled=True)
        w_down = jax.lax.all_gather(w_down, fsdp_axes, axis=2, tiled=True)

    # ---- routing (identical on every tp shard) ----
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)  # (N, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # ---- sort-based local dispatch ----
    flat_e = idx.reshape(-1)  # (N*k,)
    flat_tok = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e)
    se = flat_e[order]
    st = flat_tok[order]
    sg = flat_gate[order]
    counts = jax.ops.segment_sum(
        jnp.ones_like(se, dtype=jnp.int32), se, num_segments=E
    )
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]]
    )
    r = jnp.arange(N * k, dtype=jnp.int32) - starts[se]  # rank within expert

    m = jax.lax.axis_index(tp) if tp else 0
    local_e = se - m * E_loc
    keep = (local_e >= 0) & (local_e < E_loc) & (r < C)
    slot = jnp.where(keep, local_e * (C + 1) + r, E_loc * (C + 1) - 1)

    gathered = jnp.where(keep[:, None], x[st], 0)
    buf = jnp.zeros((E_loc * (C + 1), d), x.dtype).at[slot].add(gathered)
    buf = buf.reshape(E_loc, C + 1, d)[:, :C]  # drop overflow slot

    # ---- expert FFN (SwiGLU, f32 accumulation on the MXU) ----
    h = swiglu(
        jnp.einsum("ecd,edf->ecf", buf, w_gate,
                   preferred_element_type=jnp.float32),
        jnp.einsum("ecd,edf->ecf", buf, w_up,
                   preferred_element_type=jnp.float32),
    ).astype(x.dtype)
    y = jnp.einsum("ecf,efd->ecd", h, w_down,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    y = y.reshape(E_loc * C, d)

    # ---- combine ----
    yslot = jnp.where(keep, local_e * C + jnp.minimum(r, C - 1), 0)
    vals = jnp.where(keep[:, None], y[yslot], 0)  # (N*k, d)
    out = jnp.zeros((N, d), x.dtype).at[st].add(
        sg[:, None].astype(x.dtype) * vals
    )
    if tp:
        out = jax.lax.psum(out, tp)

    # ---- Switch-style load-balance aux loss (global mean) ----
    frac = counts.astype(jnp.float32) / jnp.float32(N * k)
    mean_prob = jnp.mean(probs, axis=0)
    aux = jnp.float32(E) * jnp.sum(frac * mean_prob)
    if dp_axes:
        aux = jax.lax.pmean(aux, dp_axes)
    if tp:
        aux = jax.lax.pmean(aux, tp)  # no-op value-wise; marks replicated
    return out, aux


def moe_ffn(
    x: jax.Array,          # (B, S, d) — replicated over tp
    router_w: jax.Array,   # (d, E)
    w_gate: jax.Array,     # (E, d, f)
    w_up: jax.Array,       # (E, d, f)
    w_down: jax.Array,     # (E, f, d)
    cfg: MoEConfig,
    topo: Topology,
) -> tuple[jax.Array, jax.Array]:
    """Returns (out (B, S, d), aux_loss scalar)."""
    B, S, d = x.shape
    # batch shards over dp when divisible; otherwise (e.g. the
    # global_batch=1 long-context decode cell) tokens stay replicated.
    shard_batch = B % topo.dp_size == 0
    n_local = (B // topo.dp_size if shard_batch else B) * S
    C = capacity(cfg, n_local)
    fsdp_axes = topo.dp_axes if topo.dp_size > 1 else ()
    dp_axes = topo.dp_axes if shard_batch and topo.dp_size > 1 else ()
    tp_spec = topo.tp_axis if topo.tp_size > 1 else None
    x_spec = P(topo.dp, None, None) if shard_batch else P(None, None, None)

    def fn(xb, rw, wg, wu, wd):
        xl = xb.reshape(-1, d)
        out, aux = _moe_local(
            xl, rw, wg, wu, wd, cfg=cfg, topo=topo, C=C,
            fsdp_axes=fsdp_axes, dp_axes=dp_axes,
        )
        if not shard_batch and topo.dp_size > 1:
            # tokens were processed redundantly on every dp shard;
            # mark the result replicated for the out_spec.
            out = jax.lax.pmean(out, topo.dp_axes)
        # mark aux replicated over the whole mesh (value already equal)
        aux = jax.lax.pmean(aux, topo.axis_names)
        return out.reshape(xb.shape), aux

    out, aux = jax.shard_map(
        fn,
        mesh=topo.mesh,
        in_specs=(
            x_spec,
            P(None, None),
            P(tp_spec, topo.dp, None),
            P(tp_spec, topo.dp, None),
            P(tp_spec, None, topo.dp),
        ),
        out_specs=(x_spec, P()),
    )(x, router_w, w_gate, w_up, w_down)
    return out, aux
