"""Expert parallelism: all_to_all token dispatch over an `ep` mesh axis.

Reference parity: `fluid/operators/collective/global_scatter_op` /
`global_gather_op` (the MoE all-to-alls) and the EP path of
`incubate/distributed/models/moe/moe_layer.py` [UNVERIFIED — empty
reference mount; SURVEY.md §2.3 EP row].

TPU-native: the reference's global_scatter ships each token's bytes to
the rank owning its expert through NCCL all-to-all.  Here experts live
as a leading dim of STACKED parameter arrays sharded over the `ep` mesh
axis, and inside shard_map one `jax.lax.all_to_all` regroups the
capacity-dispatched slot tensor [E, C, D] from token-major to
expert-major across devices (and back for combine).  Tokens shard over
EVERY mesh axis (dp x ep both carry tokens — the standard EP grid);
expert FFNs run vmapped over the local experts so each expert's matmul
is one batched MXU op.

Functions:
  * global_scatter_local / global_gather_local — the all-to-all
    regroupings, callable inside shard_map (the c_op equivalents);
  * moe_ep_forward_local — full MoE forward on local token shards;
  * ExpertParallelEngine — pure SPMD executor for an eager MoELayer:
    parameters are passed per call (stacked in-graph), so the eager
    tape / jax.grad differentiate straight through and the expert
    Layers stay the single source of truth for weights.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ...env import global_mesh

__all__ = ["global_scatter_local", "global_gather_local",
           "moe_ep_forward_local", "ExpertParallelEngine"]


def _a2a(x, *, axis, axis_size, mode):
    """Leading-dim all-to-all: the fused collective, or (overlap mode)
    the bit-exact per-peer ppermute ring whose hops XLA can schedule
    under the surrounding expert compute (PR 11 ring discipline)."""
    if mode == "overlap":
        from ...auto_parallel.moe_dispatch import ring_all_to_all_local
        return ring_all_to_all_local(x, axis=axis, axis_size=axis_size,
                                     mode=mode)
    return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                              tiled=False)


def global_scatter_local(dispatched, *, axis="ep", axis_size,
                         mode="sequential"):
    """[E, C, D] token-major slots → [E_local, P*C, D] expert-major.

    Chunk p (experts owned by device p) is sent to device p; received
    chunks stack on the slot dim (the reference's global_scatter)."""
    E, C, D = dispatched.shape
    e_loc = E // axis_size
    x = dispatched.reshape(axis_size, e_loc, C, D)
    x = _a2a(x, axis=axis, axis_size=axis_size,
             mode=mode)                          # dim0 now = source dev
    x = jnp.swapaxes(x, 0, 1)                    # [E_loc, P, C, D]
    return x.reshape(e_loc, axis_size * C, D)


def global_gather_local(expert_out, *, axis="ep", axis_size,
                        mode="sequential"):
    """Inverse of global_scatter_local: [E_local, P*C, D] → [E, C, D]."""
    e_loc, PC, D = expert_out.shape
    C = PC // axis_size
    x = expert_out.reshape(e_loc, axis_size, C, D)
    x = jnp.swapaxes(x, 0, 1)                    # [P, E_loc, C, D]
    x = _a2a(x, axis=axis, axis_size=axis_size, mode=mode)
    return x.reshape(axis_size * e_loc, C, D)


def moe_ep_forward_local(x, gating, expert_params, expert_apply,
                         dispatch_fn, *, capacity, axis="ep", axis_size,
                         mode="sequential"):
    """MoE forward on a LOCAL token shard inside shard_map.

    x: [n_local, D] tokens.  gating: (probs, topk_idx, topk_val) local
    slices (the gate itself runs globally OUTSIDE shard_map so the
    load-balancing aux loss sees the global token distribution, exactly
    like the dense layer).  expert_params: pytree with local-expert
    leading dim [E_loc, ...].  expert_apply(params_e, tokens) applies
    ONE expert.  dispatch_fn builds the (dispatched [E, C, D], combine
    [n, E, C]) pair (the GShard capacity routing shared with the dense
    MoELayer).  Returns y [n_local, D]."""
    probs, topk_idx, topk_val = gating
    dispatched, combine = dispatch_fn(x, probs, topk_idx, topk_val,
                                      capacity)
    slots = global_scatter_local(dispatched, axis=axis,
                                 axis_size=axis_size,
                                 mode=mode)             # [E_loc, P*C, D]
    out = jax.vmap(expert_apply)(expert_params, slots)
    gathered = global_gather_local(out, axis=axis, axis_size=axis_size,
                                   mode=mode)            # [E, C, D]
    y = jnp.einsum("nec,ecd->nd", combine.astype(jnp.float32),
                   gathered.astype(jnp.float32)).astype(x.dtype)
    return y


class ExpertParallelEngine:
    """Pure SPMD EP executor for an eager MoELayer.

    __call__(x_val, expert_vals, gate_vals, capacity) is a pure function
    of its inputs (differentiable; callable eagerly or under jit):
    expert_vals are the E experts' parameter arrays in expert-major
    order, stacked in-graph onto the ep-sharded expert dim.
    """

    def __init__(self, moe_layer, mesh=None, axis="ep"):
        from .pp_utils.spmd_schedule import _FunctionalSegment
        self.mesh = mesh or global_mesh()
        if self.mesh is None or axis not in self.mesh.axis_names:
            raise ValueError(f"no '{axis}' axis in mesh")
        self.axis = axis
        self.axis_size = int(self.mesh.shape[axis])
        self.moe = moe_layer
        experts = list(moe_layer.experts)
        self.n_experts = len(experts)
        if self.n_experts % self.axis_size:
            raise ValueError(
                f"{self.n_experts} experts not divisible by "
                f"ep={self.axis_size}")
        sigs = {tuple((tuple(p.shape), str(p.dtype))
                      for p in e.parameters()) for e in experts}
        if len(sigs) != 1:
            raise ValueError("EP requires homogeneous experts")
        self._seg = _FunctionalSegment([(experts[0], None)])
        self._gate_seg = _FunctionalSegment([(moe_layer.gate, None)])
        self.n_p = len(self._seg.params)
        self.expert_tensors = [p for e in experts for p in e.parameters()]
        self.gate_tensors = list(self._gate_seg.params)
        self.tok_axes = tuple(self.mesh.axis_names)

    # -- pure pieces -----------------------------------------------------
    def _gate_fn(self, xv, gate_vals):
        from ....core.autograd import no_grad
        from ....core.tensor import Tensor as T
        gate_layer = self._gate_seg.segment[0][0]
        saved = [(p, p._value) for p in self._gate_seg.params]
        try:
            for p, v in zip(self._gate_seg.params, gate_vals):
                p._value = v
            with no_grad():
                r = gate_layer(T(xv, _internal=True, stop_gradient=True))
            return tuple(t._value if isinstance(t, T) else t for t in r)
        finally:
            for p, v in saved:
                p._value = v

    def __call__(self, x_val, expert_vals, gate_vals, capacity):
        """x_val: global [N, D]; expert_vals: flat tuple of E*n_p arrays
        (expert-major); gate_vals: gate param arrays.
        Returns (y [N, D], aux)."""
        from ....incubate.distributed.models.moe.moe_layer import \
            _dispatch_combine
        axis, axis_size, n_p = self.axis, self.axis_size, self.n_p
        E = self.n_experts
        mesh = self.mesh

        # stack expert params in-graph: [E, ...] sharded over ep
        stacked = []
        for i in range(n_p):
            arr = jnp.stack([expert_vals[e * n_p + i] for e in range(E)])
            spec = P(axis, *([None] * (arr.ndim - 1)))
            try:
                arr = jax.lax.with_sharding_constraint(
                    arr, NamedSharding(mesh, spec))
            except Exception:
                pass  # eager on an un-committed value: advisory only
            stacked.append(arr)

        # gate runs globally (aux loss must see the global distribution)
        probs, topk_idx, topk_val, aux = self._gate_fn(x_val, gate_vals)

        # ep all-to-alls ride the ring-overlap machinery when the active
        # plan's probe admits it (PADDLE_TPU_OVERLAP discipline)
        from ...auto_parallel import overlap as _overlap
        from ...auto_parallel import sharding as _spmd
        a2a_mode = _overlap.select_mode(_spmd.get_mesh_plan(), axis)

        def device_fn(stacked, xl, pl, il, vl):
            return moe_ep_forward_local(
                xl, (pl, il, vl),
                list(stacked),
                lambda pv, t: self._seg(list(pv), t),
                lambda *a: _dispatch_combine(*a),
                capacity=capacity, axis=axis, axis_size=axis_size,
                mode=a2a_mode)

        tok_spec = P(self.tok_axes)
        p_specs = tuple(P(axis, *([None] * (a.ndim - 1)))
                        for a in stacked)
        fn = jax.shard_map(
            device_fn, mesh=mesh,
            in_specs=(p_specs, tok_spec, tok_spec, tok_spec, tok_spec),
            out_specs=tok_spec, check_vma=False)
        y = fn(tuple(stacked), x_val, probs, topk_idx, topk_val)
        return y, aux
