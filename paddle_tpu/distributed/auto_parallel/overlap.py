"""Tile-level compute/communication overlap for sharded matmuls.

ROADMAP item 1 / PAPERS.md "Tile-Level Activation Overlap" (arxiv
2607.02521): a tensor-parallel matmul that waits for its collective
leaves the MXU idle for the whole interconnect transfer.  This module
decomposes both TP matmul directions into per-tile ring steps inside
``shard_map`` — the same discipline as ``ops/ring_flash_attention.py`` —
so each ``ppermute`` hop is issued *before* the partial dot it does not
depend on and XLA's scheduler runs the transfer under the compute:

* **all-gather-matmul** (column-parallel input side): ``a`` is
  row-sharded over the axis, ``b`` replicated.  Each step rotates the
  resident ``a``-shard one hop while the current shard's partial dot
  lands in its output block (``out = AG(a) @ b``, replicated).
* **matmul-reduce-scatter** (row-parallel dual): ``a`` column-sharded,
  ``b`` row-sharded.  A row-tile accumulator travels the ring the
  opposite way; each step's hop carries the running partial sum while
  the next tile's dot computes (``out = RS(a @ b)``, row-scattered).

Both have a **sequential fallback** (collective completes strictly
before any compute) that is *bit-exact* against the overlapped path:

* AG direction: row-blocked dots are bit-identical to the gathered full
  dot per output row, so ``all_gather`` + one dot matches exactly.
* RS direction: the fallback reduces the full local product through a
  manual ring reduce-scatter with the **same accumulation order** as the
  overlapped schedule; tile slices of the full product are bit-equal to
  per-tile dots, so the two paths add identical summands identically.

Selection is ``pallas_gate``-style: ``PADDLE_TPU_OVERLAP``
(auto|overlap|sequential) plus a cached probe compile per mesh topology,
consulted by ``select_mode`` — the static Executor and
``MeshPlan.wrap_step`` callers pick overlapped vs sequential per step
function, and the chosen mode is part of ``plan_cache_token`` so an env
flip never reuses a stale executable.

``measured_sharded_matmul`` drives the same ring step-wise from the
host, emitting ``cat="collective"`` spans (with the axis attr the eager
collectives use) whose lifetime genuinely brackets the in-flight
``ppermute`` — overlapped mode dispatches the partial dot inside that
window, sequential mode blocks first — so the per-axis overlap ratio in
``observability.phase_breakdown()`` comes from real timeline spans.
"""
from __future__ import annotations

import logging
import math
import os
import traceback

import numpy as np

from ... import observability as obs

__all__ = [
    "ENV_OVERLAP", "OverlapProbeResult", "all_gather_matmul_local",
    "executor_linear_override", "matmul_reduce_scatter_local",
    "measured_sharded_matmul", "mode_token", "overlap_eligible",
    "overlap_flag", "overlap_report", "probe_overlap",
    "reset_overlap_cache", "select_mode", "sharded_matmul",
    "tile_arithmetic",
]

ENV_OVERLAP = "PADDLE_TPU_OVERLAP"

_logger = logging.getLogger("paddle_tpu.overlap")

#: (axis, axis_sizes) -> OverlapProbeResult, cleared by reset
_probe_results: dict = {}
#: (plan token, axis, direction, mode, shapes/dtypes) -> compiled fn
_jit_cache: dict = {}


def _jnp():
    import jax.numpy as jnp
    return jnp


def overlap_flag():
    """Normalized ``PADDLE_TPU_OVERLAP``: auto | overlap | sequential."""
    raw = os.environ.get(ENV_OVERLAP, "auto").strip().lower()
    if raw in ("", "auto"):
        return "auto"
    if raw in ("1", "on", "true", "overlap"):
        return "overlap"
    if raw in ("0", "off", "false", "sequential", "seq"):
        return "sequential"
    raise ValueError(
        f"bad {ENV_OVERLAP}={raw!r}; expected auto|overlap|sequential")


def mode_token():
    """Cache-token component for the *configured* overlap mode.

    The probe outcome is deterministic per process+mesh, so only the
    env-level configuration needs to key executable caches (MIGRATION:
    mesh cache tokens include the overlap mode).
    """
    return overlap_flag()


# ---------------------------------------------------------------------------
# Probe / selection (pallas_gate discipline)
# ---------------------------------------------------------------------------

class OverlapProbeResult:
    """Outcome of one overlap probe compile on a concrete mesh."""

    __slots__ = ("key", "ok", "error", "error_type")

    def __init__(self, key, ok, error=None, error_type=None):
        self.key = key
        self.ok = ok
        self.error = error
        self.error_type = error_type

    def to_dict(self):
        d = {"mesh": dict(self.key[1]), "axis": self.key[0],
             "ok": self.ok, "probed": True}
        if not self.ok:
            d["error"] = self.error
            d["error_type"] = self.error_type
        return d


def _probe_key(plan, axis):
    return (axis, tuple(plan.axis_sizes.items()))


def _run_probe(plan, axis):
    """Compile+run both directions at a tiny shape on the plan's mesh
    and check the overlapped path against its sequential fallback."""
    from ...analysis.diagnostics import Diagnostic, record
    jnp = _jnp()
    key = _probe_key(plan, axis)
    P = plan.axis_size(axis)
    try:
        m, k, n = 4 * P, 8, 8
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
        for direction in ("ag", "rs"):
            o = sharded_matmul(a, b, plan=plan, axis=axis,
                               direction=direction, mode="overlap")
            s = sharded_matmul(a, b, plan=plan, axis=axis,
                               direction=direction, mode="sequential")
            if not bool(jnp.all(o == s)):
                raise AssertionError(
                    f"overlapped {direction} diverged from the "
                    f"sequential fallback at the probe shape")
        result = OverlapProbeResult(key, True)
        _logger.info("overlap probe OK on mesh %s axis %s",
                     plan.describe(), axis)
    except Exception as exc:
        err = "".join(traceback.format_exception_only(type(exc), exc))
        err = err.strip()
        record(Diagnostic(
            "TPU110",
            f"overlapped sharded matmul failed its probe compile on "
            f"mesh {plan.describe()} ({type(exc).__name__}); step "
            f"functions fall back to the sequential collective-then-dot "
            f"path",
            site=f"overlap_gate[{plan.describe()}/{axis}]",
            hint=f"overlap_report() carries the full error; set "
                 f"{ENV_OVERLAP}=sequential to silence the probe",
            data={"error": err[:2000]}))
        result = OverlapProbeResult(key, False, error=err,
                                    error_type=type(exc).__name__)
        _logger.exception(
            "overlap probe FAILED on mesh %s axis %s; falling back to "
            "sequential collectives for this process", plan.describe(),
            axis)
    _probe_results[key] = result
    return result


def probe_overlap(plan, axis="tp", force=False):
    """Probe (cached) the overlapped path on ``plan``'s mesh."""
    key = _probe_key(plan, axis)
    if not force and plan.axis_size(axis) <= 1:
        return OverlapProbeResult(key, False,
                                  error=f"axis {axis!r} has size <= 1",
                                  error_type="skipped")
    result = _probe_results.get(key)
    if result is None:
        result = _run_probe(plan, axis)
    return result


def select_mode(plan, axis="tp"):
    """Per-step-function selection: ``'overlap'`` or ``'sequential'``.

    ``sequential`` when the flag forces it, there is no plan / a
    virtual plan / no >1-sized ``axis``; ``overlap`` when the flag
    forces it; under ``auto`` the cached probe decides.
    """
    flag = overlap_flag()
    if flag == "sequential":
        return "sequential"
    if plan is None or plan.is_virtual or plan.axis_size(axis) <= 1:
        return "sequential"
    if flag == "overlap":
        return "overlap"
    return "overlap" if probe_overlap(plan, axis).ok else "sequential"


def overlap_report():
    """Cached probe outcomes keyed ``'<mesh>/<axis>'``."""
    return {f"{dict(key[1])}/{key[0]}": res.to_dict()
            for key, res in _probe_results.items()}


def reset_overlap_cache():
    _probe_results.clear()
    _jit_cache.clear()


# ---------------------------------------------------------------------------
# Eligibility arithmetic (shared with the TPU504 audit)
# ---------------------------------------------------------------------------

def overlap_eligible(dim, axis_size):
    """A dimension tiles cleanly iff it divides by the tile count
    (= axis size); a ragged last tile forces padded transfers."""
    return int(axis_size) > 1 and int(dim) % int(axis_size) == 0


def tile_arithmetic(dim, axis_size):
    """Human-readable tile math for diagnostics."""
    dim, P = int(dim), int(axis_size)
    if P <= 1:
        return f"{dim} rows, 1 tile (axis size {P}: nothing to overlap)"
    if dim % P == 0:
        return f"{dim} % {P} == 0 -> {P} tiles of {dim // P}"
    pad = ((dim + P - 1) // P) * P
    return (f"{dim} % {P} == {dim % P} -> last tile ragged "
            f"({dim - (P - 1) * ((dim + P - 1) // P)} of "
            f"{(dim + P - 1) // P} rows); pad to {pad}")


# ---------------------------------------------------------------------------
# Per-shard ring schedules (call inside shard_map)
# ---------------------------------------------------------------------------

def _dot(x, w):
    """Partial-tile dot.  bf16 inputs accumulate in f32 (cast back at
    the end of the schedule) so tile count never changes the precision
    story; f32 stays plain so bit-exactness claims are about schedule
    order only."""
    jnp = _jnp()
    if x.dtype == jnp.bfloat16 or w.dtype == jnp.bfloat16:
        return jnp.matmul(x, w, preferred_element_type=jnp.float32)
    return jnp.matmul(x, w)


def _out_dtype(a, b):
    return _jnp().promote_types(a.dtype, b.dtype)


def _acc_dtype(a, b):
    """Dtype the ring accumulates in (f32 for bf16 inputs)."""
    jnp = _jnp()
    if a.dtype == jnp.bfloat16 or b.dtype == jnp.bfloat16:
        return jnp.float32
    return _out_dtype(a, b)


def all_gather_matmul_local(a, b, *, axis, axis_size, mode="overlap"):
    """Per-shard ``all_gather(a) @ b``: ``a`` = [m_local, k] (dim 0
    sharded over ``axis``), ``b`` = [k, n] replicated.  Returns the
    full [m, n] product on every shard.

    Overlapped: each step issues the next shard's ``ppermute`` hop
    *before* the resident shard's partial dot — the two are
    independent, so the transfer runs under the MXU.  Sequential:
    the whole gather completes, then one dot (bit-exact vs overlapped:
    row-blocked dots are per-row identical to the full dot).
    """
    import jax
    jnp = _jnp()
    P = int(axis_size)
    if mode == "sequential" or P <= 1:
        a_full = jax.lax.all_gather(a, axis, axis=0, tiled=True) \
            if P > 1 else a
        return _dot(a_full, b).astype(_out_dtype(a, b))
    m_local = a.shape[0]
    me = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % P) for i in range(P)]
    out = jnp.zeros((m_local * P, b.shape[-1]), _acc_dtype(a, b))
    a_cur = a
    for r in range(P):
        # hop first: independent of this step's dot -> XLA overlaps
        a_nxt = jax.lax.ppermute(a_cur, axis, perm) if r < P - 1 else None
        partial = _dot(a_cur, b)
        src = (me - r) % P          # original owner of the resident shard
        start = src * m_local
        out = jax.lax.dynamic_update_slice(
            out, partial, (start, jnp.zeros((), start.dtype)))
        a_cur = a_nxt
    return out.astype(_out_dtype(a, b))


def matmul_reduce_scatter_local(a, b, *, axis, axis_size,
                                mode="overlap"):
    """Per-shard ``reduce_scatter(a @ b)``: ``a`` = [m, k_local]
    (contraction dim sharded over ``axis``), ``b`` = [k_local, n].
    Returns this shard's [m // axis_size, n] row tile of the summed
    product.

    Overlapped: a row-tile accumulator rides the ring (device ``i`` ->
    ``i-1``); each step's hop carries the running sum while the next
    tile's partial dot computes.  Sequential: the full local product
    completes first, then a manual ring reduce-scatter with the *same*
    accumulation order — tile slices of the full product are bit-equal
    to per-tile dots, so the two modes are bit-exact f32.
    """
    import jax
    P = int(axis_size)
    dt = _out_dtype(a, b)
    if P <= 1:
        return _dot(a, b).astype(dt)
    m_local = a.shape[0] // P
    me = jax.lax.axis_index(axis)
    perm = [(i, (i - 1) % P) for i in range(P)]

    if mode == "sequential":
        full = _dot(a, b)           # compute completes before any hop

        def tile(t):
            start = t * m_local
            return jax.lax.dynamic_slice(
                full, (start, _jnp().zeros((), start.dtype)),
                (m_local, full.shape[1]))

        acc = tile((me + 1) % P)
        for r in range(1, P):
            acc = jax.lax.ppermute(acc, axis, perm) + tile((me + 1 + r) % P)
        return acc.astype(dt)

    def tile_dot(t):
        start = t * m_local
        sl = jax.lax.dynamic_slice(
            a, (start, _jnp().zeros((), start.dtype)),
            (m_local, a.shape[1]))
        return _dot(sl, b)

    acc = tile_dot((me + 1) % P)
    for r in range(1, P):
        # hop the running sum while the next tile's dot computes
        acc_in = jax.lax.ppermute(acc, axis, perm)
        acc = acc_in + tile_dot((me + 1 + r) % P)
    return acc.astype(dt)


# ---------------------------------------------------------------------------
# Global-array wrapper (pads ragged tiles, caches compiled fns)
# ---------------------------------------------------------------------------

def _pad_to(x, dim, multiple):
    jnp = _jnp()
    size = x.shape[dim]
    rem = size % multiple
    if rem == 0:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[dim] = (0, multiple - rem)
    return jnp.pad(x, pad), size


def _compiled(plan, axis, direction, mode, a, b):
    import jax
    from jax.sharding import PartitionSpec as P
    key = (plan.cache_token(), axis, direction, mode,
           a.shape, str(a.dtype), b.shape, str(b.dtype))
    fn = _jit_cache.get(key)
    if fn is not None:
        return fn
    size = plan.axis_size(axis)
    if direction == "ag":
        local = lambda al, bl: all_gather_matmul_local(  # noqa: E731
            al, bl, axis=axis, axis_size=size, mode=mode)
        in_specs = (P(axis, None), P(None, None))
        out_specs = P(None, None)
    else:
        local = lambda al, bl: matmul_reduce_scatter_local(  # noqa: E731
            al, bl, axis=axis, axis_size=size, mode=mode)
        in_specs = (P(None, axis), P(axis, None))
        out_specs = P(axis, None)
    mapped = jax.shard_map(local, mesh=plan.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    with obs.span(f"compile:sharded_matmul[{direction}/{mode}]",
                  cat="compile", mesh=plan.describe(), axis=axis):
        fn = jax.jit(mapped).lower(a, b).compile()
    _jit_cache[key] = fn
    return fn


def sharded_matmul(a, b, *, direction, plan=None, axis="tp", mode=None):
    """Global-array entry: ``a @ b`` through the overlapped (or
    sequential) ring schedule on ``plan``'s mesh.

    ``direction='ag'``: ``a`` [m, k] row-sharded over ``axis``, ``b``
    replicated.  ``direction='rs'``: contraction dim sharded across
    both operands, output rows reduce-scattered (the global result is
    still the full product).  Ragged dims are zero-padded to the tile
    count and sliced back — uneven last tiles work in both modes.
    """
    from . import sharding as spmd
    jnp = _jnp()
    if plan is None:
        plan = spmd.get_mesh_plan()
    if plan is None or plan.is_virtual or plan.axis_size(axis) <= 1:
        return _dot(a, b).astype(_out_dtype(a, b))
    if mode is None:
        mode = select_mode(plan, axis)
    if direction not in ("ag", "rs"):
        raise ValueError(f"direction must be 'ag' or 'rs', got "
                         f"{direction!r}")
    P = plan.axis_size(axis)
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    m = a.shape[0]
    a, _ = _pad_to(a, 0, P)
    if direction == "rs":
        a, _ = _pad_to(a, 1, P)
        b, _ = _pad_to(b, 0, P)
    fn = _compiled(plan, axis, direction, mode, a, b)
    with obs.span(f"dispatch:sharded_matmul[{direction}]",
                  cat="dispatch", mesh=plan.describe(), axis=axis,
                  mode=mode):
        out = fn(a, b)
    return out[:m] if out.shape[0] != m else out


# ---------------------------------------------------------------------------
# Measured host-driven ring (timeline evidence for the overlap ratio)
# ---------------------------------------------------------------------------

def _measured_fns(plan, axis, a, b):
    import jax
    from jax.sharding import PartitionSpec as P
    key = ("measured", plan.cache_token(), axis,
           a.shape, str(a.dtype), b.shape, str(b.dtype))
    fns = _jit_cache.get(key)
    if fns is not None:
        return fns
    size = plan.axis_size(axis)
    perm = [(i, (i + 1) % size) for i in range(size)]
    rot = jax.shard_map(lambda x: jax.lax.ppermute(x, axis, perm),
                        mesh=plan.mesh, in_specs=P(axis, None),
                        out_specs=P(axis, None), check_vma=False)
    dot = jax.shard_map(
        lambda al, bl: _dot(al, bl).astype(_out_dtype(al, bl)),
        mesh=plan.mesh, in_specs=(P(axis, None), P(None, None)),
        out_specs=P(axis, None), check_vma=False)
    fns = (jax.jit(rot).lower(a).compile(),
           jax.jit(dot).lower(a, b).compile())
    _jit_cache[key] = fns
    return fns


def measured_sharded_matmul(a, b, *, plan=None, axis="tp", mode=None):
    """Drive the all-gather-matmul ring step-wise from the host so the
    timeline records *real* collective/compute spans.

    Each ring hop runs as its own async device call inside a
    ``cat="collective"`` span carrying the axis attr (the same shape
    the eager collectives emit).  Overlapped mode dispatches the
    partial dot while that hop is in flight — the dispatch span nests
    inside the collective span, which is exactly what
    ``phase_breakdown()``'s per-axis overlap ratio measures.
    Sequential mode blocks on the hop first, so its ratio is ~0.

    Returns the full ``a @ b`` product (row-padded dims sliced back).
    """
    import jax
    from . import sharding as spmd
    jnp = _jnp()
    if plan is None:
        plan = spmd.get_mesh_plan()
    if plan is None or plan.is_virtual or plan.axis_size(axis) <= 1:
        raise ValueError("measured_sharded_matmul needs a real plan "
                         f"with axis {axis!r} > 1")
    if mode is None:
        mode = select_mode(plan, axis)
    P = plan.axis_size(axis)
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    m = a.shape[0]
    a, _ = _pad_to(a, 0, P)
    rot, dot = _measured_fns(plan, axis, a, b)
    nb = int(a.size) * a.dtype.itemsize
    out = None
    a_cur = a
    for r in range(P):
        if mode == "overlap" and r < P - 1:
            with obs.span("collective:overlap.ppermute", cat="collective",
                          axis=axis, bytes=nb, mode=mode):
                a_nxt = rot(a_cur)
                with obs.span("dispatch:overlap.partial_dot",
                              cat="dispatch", axis=axis, mode=mode):
                    part = dot(a_cur, b)
                    jax.block_until_ready(part)
                jax.block_until_ready(a_nxt)
        elif mode == "overlap":
            a_nxt = None
            with obs.span("dispatch:overlap.partial_dot", cat="dispatch",
                          axis=axis, mode=mode):
                part = dot(a_cur, b)
                jax.block_until_ready(part)
        else:
            a_nxt = None
            if r < P - 1:
                with obs.span("collective:overlap.ppermute",
                              cat="collective", axis=axis, bytes=nb,
                              mode=mode):
                    a_nxt = rot(a_cur)
                    jax.block_until_ready(a_nxt)
            with obs.span("dispatch:overlap.partial_dot", cat="dispatch",
                          axis=axis, mode=mode):
                part = dot(a_cur, b)
                jax.block_until_ready(part)
        if r == 0:
            # step 0's gathered partials already tile the full product
            # (device j holds shard j); later steps replicate it.
            out = part
        if a_nxt is not None:
            a_cur = a_nxt
    return out[:m] if out.shape[0] != m else out


# ---------------------------------------------------------------------------
# Executor hook: route eligible row-parallel linears through the ring
# ---------------------------------------------------------------------------

def executor_linear_override(plan, mode, routed=None):
    """``op_override`` for ``static.executor.run_program_ops``.

    Intercepts ``linear`` / ``linear_act`` ops whose weight is purely
    row-parallel (legalized spec ``P('tp', ...)`` with nothing on the
    output dim) and replaces the GSPMD all-reduce with a nested
    ``shard_map`` island: ``matmul_reduce_scatter_local`` (the
    overlapped half) + a tiled ``all_gather`` — a decomposed
    all-reduce whose reduce half hides under the partial dots.
    Ineligible ops return ``NotImplemented`` and fall through to the
    plain impl (GSPMD inserts its collective as before).

    ``routed`` (a list, optional) collects the spmd names of routed
    weights at trace time — surfaced in the executor cache entry.
    """
    if plan is None or plan.is_virtual or mode != "overlap" \
            or plan.axis_size("tp") <= 1:
        return None
    import jax
    from jax.sharding import PartitionSpec as P
    from . import sharding as spmd
    from ...nn.functional.common import _apply_act

    tp = plan.axis_size("tp")
    data_axes = plan.data_axes()

    def override(op, vals):
        if op.type not in ("linear", "linear_act"):
            return NotImplemented
        w_t = op.inputs[1]
        if isinstance(w_t, spmd_variable_types()):
            return NotImplemented          # weight is a graph temp
        x, w = vals[0], vals[1]
        bias = vals[2] if len(vals) > 2 else None
        act = op.attrs.get("act") if op.type == "linear_act" else None
        if w.ndim != 2 or x.ndim < 2:
            return NotImplemented
        spec = plan.spec_for(spmd.spmd_name(w_t), w.shape)
        entries = tuple(spec)
        if not entries or entries[0] != "tp":
            return NotImplemented          # not row-parallel
        if any(e is not None for e in entries[1:]):
            return NotImplemented          # fsdp/tp also on out dim
        k = w.shape[0]
        batch0 = x.shape[0]
        dfac = math.prod(plan.axis_sizes[a] for a in data_axes) \
            if data_axes else 1
        if dfac > 1 and batch0 % dfac != 0:
            dfac = 1                       # batch replicated (batch_spec)
        rows_local = (batch0 // dfac) * math.prod(x.shape[1:-1])
        if k % tp != 0 or rows_local % tp != 0:
            return NotImplemented          # ragged tiles: leave to GSPMD
        if x.shape[-1] != k:
            return NotImplemented

        x_batch = data_axes if len(data_axes) > 1 else (
            data_axes[0] if data_axes else None)
        x_spec = P(*((x_batch if dfac > 1 else None,)
                     + (None,) * (x.ndim - 2) + ("tp",)))
        out_spec = P(*((x_batch if dfac > 1 else None,)
                       + (None,) * (x.ndim - 1)))

        def island(xl, wl):
            x2 = xl.reshape((-1, xl.shape[-1]))
            part = matmul_reduce_scatter_local(
                x2, wl, axis="tp", axis_size=tp, mode="overlap")
            full = jax.lax.all_gather(part, "tp", axis=0, tiled=True)
            return full.reshape(xl.shape[:-1] + (wl.shape[-1],))

        mapped = jax.shard_map(island, mesh=plan.mesh,
                               in_specs=(x_spec, P("tp", None)),
                               out_specs=out_spec, check_vma=False)
        z = mapped(x, w)
        if bias is not None:
            z = z + bias
        if act is not None:
            z = _apply_act(z, act)
        if routed is not None:
            routed.append(spmd.spmd_name(w_t))
        return z

    return override


def spmd_variable_types():
    """The framework Variable type(s) — weights must be captured
    tensors, not graph temporaries, for rule lookup to mean anything."""
    from ...static.framework import Variable
    return (Variable,)
