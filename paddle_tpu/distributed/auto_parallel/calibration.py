"""Measured alpha-beta calibration for the auto-parallel cost model.

VERDICT r4 weak #7 / next #10: the planner's alpha-beta model was
"effectively uncalibrated" — ordering invariants had been checked
against a single measured psum point.  This module closes the loop:

  * :func:`measure_collectives` times real ``psum`` / ``all_gather`` /
    ``ppermute`` collectives (via ``shard_map`` over the current mesh)
    across a size sweep, per mesh axis size;
  * :func:`fit_alpha_beta` least-squares fits ``t = alpha * steps +
    wire_bytes / beta`` per collective kind — the same functional form
    :func:`..cost_model.comm_cost_seconds` evaluates;
  * :func:`save_fit` / :func:`load_fit` persist the fit
    (``.bench_cache/comm_fit.json`` by default, override with
    ``PADDLE_TPU_COMM_FIT``), and :func:`install_fit` makes
    ``comm_cost_seconds`` — and therefore every ``Planner`` decision —
    consume the measured constants instead of the v5e datasheet
    defaults.

Reference parity: the reference's auto-parallel cost model ships
cluster profiles measured by its own collective benchmark
(`auto_parallel/static/cost/comm_op_cost.py` + cluster topology json)
[UNVERIFIED — empty reference mount; SURVEY.md §2.3 auto-parallel row].
The TPU-native redesign measures XLA collectives on the actual mesh
(CPU ring in tests, ICI when run on hardware) rather than tabulating
NCCL primitives.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

__all__ = [
    "measure_collectives", "fit_alpha_beta", "save_fit", "load_fit",
    "install_fit", "default_fit_path", "calibrate",
]


def default_fit_path():
    p = os.environ.get("PADDLE_TPU_COMM_FIT")
    if p:
        return p
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    return os.path.join(root, ".bench_cache", "comm_fit.json")


def _collective_fn(kind, axis):
    import jax
    import jax.numpy as jnp

    if kind == "all_reduce":
        def f(x):
            return jax.lax.psum(x, axis)
    elif kind == "all_gather":
        def f(x):
            return jax.lax.all_gather(x, axis)
    elif kind == "reduce_scatter":
        def f(x):
            return jax.lax.psum_scatter(x, axis, tiled=True)
    elif kind == "permute":
        def f(x):
            n = jax.lax.axis_size(axis)
            perm = [(i, (i + 1) % n) for i in range(n)]
            return jax.lax.ppermute(x, axis, perm)
    else:
        raise ValueError(f"unknown collective kind {kind!r}")
    return f


def measure_collectives(mesh, axis, sizes=None, kinds=None, reps=5):
    """Time collectives over ``mesh``'s ``axis`` at each payload size.

    ``sizes`` are PER-SHARD payload bytes (f32).  Returns
    ``{kind: [(nbytes, seconds), ...]}`` with ``nbytes`` converted to
    the GLOBAL-array convention ``comm_cost_seconds`` uses (gathered
    size for all_gather), median wall seconds of ``reps`` synced calls.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    sizes = sizes or [1 << 12, 1 << 16, 1 << 20, 1 << 22]
    kinds = kinds or ["all_reduce", "all_gather", "reduce_scatter",
                      "permute"]
    n = int(mesh.shape[axis])
    out = {k: [] for k in kinds}
    for kind in kinds:
        f = _collective_fn(kind, axis)
        for nbytes in sizes:
            elems = max(n, nbytes // 4)
            # global array: one shard of `elems` per mesh slice
            xs = jnp.zeros((n * elems,), jnp.float32) + 1.0
            sharded = jax.device_put(
                xs, NamedSharding(mesh, P(axis)))
            g = jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P(axis), check_vma=False,
                out_specs=P(axis) if kind in ("reduce_scatter",
                                              "permute", "all_reduce")
                else P()))
            jax.block_until_ready(g(sharded))  # compile
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(g(sharded))
                ts.append(time.perf_counter() - t0)
            # record in comm_cost_seconds' GLOBAL-array convention: the
            # per-shard payload here is `elems` f32; all_gather's
            # logical array is the GATHERED one (n x larger)
            shard_bytes = float(elems * 4)
            logical = shard_bytes * n if kind == "all_gather" \
                else shard_bytes
            out[kind].append((logical, float(np.median(ts))))
    return out


def fit_alpha_beta(samples, axis_size):
    """Least-squares ``t = alpha * steps + wire / beta`` per kind.

    ``samples``: {kind: [(nbytes, seconds)]}.  Returns
    {kind: {"alpha": s/step, "beta": bytes/s}} with both clamped
    positive (a negative LSQ intercept collapses to the smallest
    observed latency share).
    """
    from .cost_model import ring_steps_wire
    fits = {}
    for kind, pts in samples.items():
        if len(pts) < 2:
            continue
        rows, ts = [], []
        for nbytes, sec in pts:
            steps, wire = ring_steps_wire(kind, nbytes, axis_size)
            rows.append([float(steps), wire])
            ts.append(sec)
        A = np.asarray(rows)
        t = np.asarray(ts)
        (a, inv_b), *_ = np.linalg.lstsq(A, t, rcond=None)
        if a <= 0:
            # latency hid under the wire term: charge the smallest
            # observed time fully to alpha
            a = max(min(t) / max(A[:, 0].max(), 1.0), 1e-9)
        if inv_b <= 0:
            inv_b = 1e-12  # effectively free wire: bandwidth-unbound
        fits[kind] = {"alpha": float(a), "beta": float(1.0 / inv_b)}
    return fits


def save_fit(fits, axis_size, platform, path=None):
    path = path or default_fit_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    payload = {
        "axis_size": int(axis_size),
        "platform": str(platform),
        "captured_unix": int(time.time()),
        "fits": fits,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def load_fit(path=None):
    path = path or default_fit_path()
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def install_fit(fits):
    """Make ``comm_cost_seconds`` (and every Planner) use ``fits``."""
    from . import cost_model
    cost_model._MEASURED_FIT = dict(fits)


def calibrate(mesh, axis, install=True, save=True, **kw):
    """Measure → fit → (install, persist).  Returns the fit dict."""
    import jax
    samples = measure_collectives(mesh, axis, **kw)
    fits = fit_alpha_beta(samples, int(mesh.shape[axis]))
    if install:
        install_fit(fits)
    if save:
        save_fit(fits, int(mesh.shape[axis]),
                 jax.devices()[0].platform)
    return fits
