"""Engine lint at the jaxpr level: trace, never run.

``lint_engine`` builds the exact shard_map program ``make_engine``
would jit for a given :class:`EngineConfig` and partition shape, traces
it to a jaxpr with abstract inputs (no devices touched, no compile),
and walks the superstep ``while`` body for hazards the type system
does not catch:

  host-callback        a callback/infeed primitive inside the hot loop
                       — serializes every superstep on the host.
  weak-scalar          weak-typed scalar arithmetic in the hot loop: a
                       Python constant whose promotion can silently
                       widen dtypes or fork the jit cache (retrace)
                       when a caller feeds the same value strongly
                       typed.
  f64-promotion        any float64/int64 value anywhere in the step —
                       the engine state is f32/i32 by design; f64
                       doubles exchange bytes silently.
  payload-overflow     an exchange (all_to_all) payload whose dtype
                       cannot represent the vertex-index range or
                       carries fewer mantissa bits than the index
                       needs — the gate ROADMAP item 4's u16/bf16
                       quantized exchange must pass.
  payload-plane        sparse exchange payload whose axis-1 extent is
                       not the expected planes x slot_cap layout — a
                       shape mismatch between the sparse and dense
                       paths' collectives.
  dead-branch          a cond whose predicate is a trace-time literal
                       — one side is dead code that still costs trace
                       time and obscures the spec grid.
  fused-kernel-escape  relax_impl requests the fused superstep kernel
                       but the traced step contains no pallas_call —
                       the engine silently fell back to the reference
                       relax path.

Each finding carries the engine source line (from jaxpr source_info)
when available.  ``lint_grid`` dedupes traces across the spec grid:
partitioners relabel data, not programs, so one trace covers every
partitioner at a given (hierarchy, exchange) point.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.analyze.findings import Finding
from repro.core.engine import EngineConfig, build_step
from repro.core.frontier import frontier_caps, payload_plane_words

#: primitives that force a host round-trip
_HOST_PRIMS = (
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "infeed", "outfeed",
)

#: primitives whose weak-typed *output* indicates a Python scalar
#: constant entering hot-loop arithmetic (comparisons excluded — a
#: weak bool is inert; converts excluded — they are the fix)
_WEAK_ARITH_PRIMS = (
    "add", "sub", "mul", "div", "rem", "max", "min", "select_n",
    "floor", "pow", "integer_pow", "neg",
)

#: collective primitives (jaxpr names under shard_map)
_COLLECTIVE_PRIMS = (
    "all_to_all", "psum", "pmin", "pmax", "ppermute", "all_gather",
)


@dataclasses.dataclass(frozen=True)
class StepShape:
    """Abstract partition shape the engine is traced at."""

    n_local: int = 64
    rows: int = 80
    width: int = 8
    n_parts: int = 1

    @property
    def n_pad(self) -> int:
        return self.n_parts * self.n_local


def _source_line(eqn) -> Optional[str]:
    """file:line of the eqn's user frame, best effort."""
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info)
        if frame is None:
            return None
        fname = frame.file_name.split("/")[-1]
        return f"{fname}:{frame.start_line}"
    except Exception:  # noqa: BLE001 — diagnostics only
        return None


def _walk(jaxpr, visit, path=""):
    """Visit every eqn recursively; ``path`` tracks the enclosing
    higher-order primitives (e.g. '/while/cond')."""
    for eqn in jaxpr.eqns:
        visit(eqn, path)
        name = eqn.primitive.name
        for v in eqn.params.values():
            vals = v if isinstance(v, (list, tuple)) else [v]
            for x in vals:
                inner = getattr(x, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    _walk(inner, visit, path + "/" + name)
                elif inner is not None and hasattr(inner, "jaxpr"):
                    _walk(inner.jaxpr, visit, path + "/" + name)
                elif hasattr(x, "eqns"):
                    _walk(x, visit, path + "/" + name)


def trace_step(
    cfg: EngineConfig,
    shape: StepShape = StepShape(),
    mesh=None,
):
    """The jaxpr ``make_engine`` would jit, traced abstractly.

    Builds the same shard_map-wrapped superstep loop (single-query
    path) and traces it with ShapeDtypeStruct inputs — no device
    buffers, no XLA compile.  Returns the ClosedJaxpr."""
    if mesh is None:
        mesh = jax.make_mesh((1,), ("data",))
    axis_names = tuple(mesh.axis_names)
    mesh_shape = tuple(mesh.devices.shape)
    n_parts = int(np.prod(mesh_shape))
    # the trace is per-program: n_parts enters only through static
    # shapes, so trace at the mesh's true part count
    sh = StepShape(shape.n_local, shape.rows, shape.width, n_parts)
    loop = build_step(cfg, axis_names, mesh_shape, sh.n_local, n_parts)

    def local(row_src, col, wgt, D, T, L):
        out = loop(row_src[0], col[0], wgt[0], D[0], T[0], L[0])
        return (out[0][None],) + out[1:]

    spec = P(axis_names)
    sharded = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec,) * 6,
        out_specs=(spec,) + (P(),) * 10,
        check_vma=False,  # as make_engine
    )
    s = jax.ShapeDtypeStruct
    args = (
        s((n_parts, sh.rows), jnp.int32),
        s((n_parts, sh.rows, sh.width), jnp.int32),
        s((n_parts, sh.rows, sh.width), jnp.float32),
        s((n_parts, sh.n_local + 1), jnp.float32),
        s((n_parts, sh.n_local + 1), jnp.float32),
        s((n_parts, sh.n_local + 1), jnp.float32),
    )
    return jax.make_jaxpr(sharded)(*args), sh


#: HLO shape dtype names -> numpy (bf16/f8 handled separately below)
_HLO_DTYPES = {
    "pred": np.bool_, "s8": np.int8, "u8": np.uint8,
    "s16": np.int16, "u16": np.uint16, "s32": np.int32,
    "u32": np.uint32, "s64": np.int64, "u64": np.uint64,
    "f16": np.float16, "f32": np.float32, "f64": np.float64,
}


def payload_index_capacity(dtype) -> int:
    """Largest vertex index a payload plane of ``dtype`` can carry
    exactly (bit-exact for integer planes, contiguous-integer range
    for float planes used arithmetically).  Accepts numpy/jnp dtypes
    and HLO shape names ('u16', 'bf16', 'f8e4m3fn')."""
    if isinstance(dtype, str) and dtype in _HLO_DTYPES:
        dtype = _HLO_DTYPES[dtype]
    elif isinstance(dtype, str) and dtype.startswith(("bf16", "f8")):
        return 1 << 8 if dtype == "bf16" else 1 << 3
    dt = np.dtype(dtype)
    if dt.kind in ("i", "u"):
        return int(np.iinfo(dt).max)
    if dt == np.float64:
        return 1 << 53
    if dt == np.float32:
        return 1 << 24
    if dt == np.float16:
        return 1 << 11
    # bf16 and the f8s — 8- and 3/2-bit mantissas
    name = getattr(dt, "name", str(dtype))
    if "bfloat16" in name or "bf16" in str(dtype):
        return 1 << 8
    return 1 << 3


def lint_engine(
    cfg: EngineConfig,
    shape: StepShape = StepShape(),
    mesh=None,
    subject: Optional[str] = None,
) -> list:
    """Trace ``build_step`` for ``cfg`` and lint the superstep body.
    Returns [Finding]."""
    subject = subject or f"{cfg.hierarchy.name}/{cfg.exchange}"
    try:
        closed, sh = trace_step(cfg, shape, mesh)
    except Exception as e:  # noqa: BLE001 — surface as a finding
        return [Finding(
            pass_name="jaxpr", rule="trace-fails", severity="error",
            subject=subject,
            message=f"build_step does not trace: {e}",
        )]
    out: list = []
    sparse = cfg.exchange in ("sparse", "auto")
    _, slot_cap = frontier_caps(
        sh.rows, sh.width, sh.n_local, sh.n_parts, cfg.frontier_cap
    )
    use_level = cfg.hierarchy.needs_level
    nplanes = 2 if use_level else 1
    expected_a2a_ax1 = {
        payload_plane_words(slot_cap, use_level, cfg.payload),
        sh.n_local,                  # dense reduce-scatter transpose
    }
    saw_pallas = [False]

    def visit(eqn, path):
        prim = eqn.primitive.name
        in_loop = "/while" in path
        src = _source_line(eqn)
        if prim == "pallas_call":
            saw_pallas[0] = True

        if prim in _HOST_PRIMS:
            out.append(Finding(
                "jaxpr", "host-callback",
                "error" if in_loop else "warn", subject,
                f"host primitive {prim!r} "
                f"{'inside the superstep loop' if in_loop else 'in the step'}"
                " — every superstep would synchronize with the host",
                source=src,
            ))

        for ov in eqn.outvars:
            av = getattr(ov, "aval", None)
            dt = getattr(av, "dtype", None)
            if dt is not None and np.dtype(dt).itemsize > 4:
                out.append(Finding(
                    "jaxpr", "f64-promotion", "error", subject,
                    f"{prim} produces {np.dtype(dt).name} — a weak-"
                    "typed Python constant is widening the f32/i32 "
                    "engine state (2x exchange bytes, silent)",
                    source=src,
                ))
            if (
                in_loop
                and prim in _WEAK_ARITH_PRIMS
                and getattr(av, "weak_type", False)
                and getattr(av, "shape", None) == ()
            ):
                out.append(Finding(
                    "jaxpr", "weak-scalar", "warn", subject,
                    f"weak-typed scalar {prim} in the superstep loop "
                    "— a Python constant entered hot-loop arithmetic; "
                    "pin it (jnp.int32/jnp.float32) so dtypes cannot "
                    "drift and the jit cache cannot fork",
                    source=src,
                ))

        if prim == "all_to_all" and in_loop:
            for iv in eqn.invars:
                av = getattr(iv, "aval", None)
                if av is None or not getattr(av, "shape", None):
                    continue
                cap = payload_index_capacity(av.dtype)
                if cap < sh.n_local:
                    out.append(Finding(
                        "jaxpr", "payload-overflow", "error", subject,
                        f"exchange payload dtype {np.dtype(av.dtype).name} "
                        f"can only index {cap} vertices exactly but "
                        f"n_local={sh.n_local} — quantized payloads "
                        "must keep an exact index plane",
                        source=src,
                    ))
                if (
                    sparse
                    and len(av.shape) == 2
                    and av.shape[0] == sh.n_parts
                    and av.shape[1] not in expected_a2a_ax1
                    and av.shape[1] != nplanes * sh.n_local
                ):
                    out.append(Finding(
                        "jaxpr", "payload-plane", "error", subject,
                        f"sparse exchange payload shape {av.shape} "
                        f"does not match the planes x slot_cap layout "
                        f"(expected axis-1 in {sorted(expected_a2a_ax1)} "
                        f"or {nplanes * sh.n_local}) — sparse and "
                        "dense paths would unpack different bytes",
                        source=src,
                    ))

        if prim == "cond":
            pred = eqn.invars[0]
            if not hasattr(pred, "count"):  # a Literal, not a Var
                out.append(Finding(
                    "jaxpr", "dead-branch", "warn", subject,
                    "cond predicate is a trace-time constant "
                    f"({getattr(pred, 'val', '?')}) — one branch is "
                    "dead code; resolve it statically like the auto-"
                    "exchange shortcut does",
                    source=src,
                ))

    _walk(closed.jaxpr, visit)
    if (
        cfg.relax_impl.startswith("fused")
        and sparse
        and not saw_pallas[0]
    ):
        out.append(Finding(
            "jaxpr", "fused-kernel-escape", "warn", subject,
            "relax_impl requests the fused superstep kernel but no "
            "pallas_call appears in the traced step — the engine "
            "silently fell back to the reference relax (non-min-plus "
            "processing or a level-bearing hierarchy); drop '/fused' "
            "or switch to an sssp-shaped spec",
        ))
    return out


def lint_grid(
    configs,
    shape: StepShape = StepShape(),
    mesh=None,
) -> dict:
    """Lint many EngineConfigs, deduping identical traces.  Returns
    {subject: [Finding]} with one entry per distinct (hierarchy,
    exchange, frontier_cap, relax_impl) program."""
    seen: dict = {}
    for cfg in configs:
        key = (cfg.hierarchy, cfg.exchange, cfg.frontier_cap,
               cfg.relax_impl, cfg.collect_metrics, cfg.payload)
        if key in seen:
            continue
        subject = f"{cfg.hierarchy.name}/{cfg.exchange}"
        seen[key] = (subject, lint_engine(cfg, shape, mesh, subject))
    return {subj: fs for subj, fs in seen.values()}
