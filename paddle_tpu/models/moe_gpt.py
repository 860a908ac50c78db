"""MoE GPT: the bundled Mixture-of-Experts decoder LM.

Same skeleton as `models/gpt.py` (blocks reuse `GPTAttention`, so the
serving engine's paged `cache.attend` path and the flash kernel route
identically), with every block's dense MLP replaced by a dropless
top-k expert MLP:

  * the router scores each token against ``num_experts`` experts and
    keeps the top-k (renormalized — the weights of the kept experts
    sum to 1, so a model whose experts are initialized identically is
    numerically the dense model: the parity tests' iso-config twin);
  * routing is DROPLESS (`distributed.auto_parallel.moe_dispatch`):
    every assignment gets a row in a block-aligned grouped buffer —
    imbalance costs padding, never quality;
  * expert FFNs are STACKED parameters ``w1 [E, H, I]`` / ``w2 [E, I,
    H]`` computed by the grouped-expert Pallas matmul
    (`ops.pallas_grouped`, XLA composite fallback when the gate is
    off);
  * under a mesh with an ``ep`` axis the stacked experts shard over it
    and each device computes only its own experts' blocks inside a
    ``shard_map`` island (`MOE_GPT_RULES` carries the ``P("ep", ...)``
    specs for the SPMD executor; `MeshPlan.shrink` re-legalizes them
    when ``ep`` collapses on elastic recovery).

Per-token routing is row-independent, so serving's ragged batch
packing never changes a token's expert assignment — moe_gpt serves
through the unified ragged step like any dense GPT.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from .. import nn
from ..nn import functional as F  # noqa: F401 (criterion parity imports)
from ..nn import initializer as I
from .generation import GenerationMixin
from .gpt import (GPTAttention, GPTConfig, GPTPretrainingCriterion,
                  paged_kv_spec)

__all__ = [
    "MoEGPTConfig", "MoEMLP", "MoEGPTBlock", "MoEGPTModel",
    "MoEGPTForCausalLM", "MoEGPTPretrainingCriterion",
]


@dataclass
class MoEGPTConfig(GPTConfig):
    num_experts: int = 4
    top_k: int = 2
    #: weight on the Switch-style load-balance auxiliary loss
    router_aux_weight: float = 0.01


def _moe_mlp_compute(x, rw, w1, b1, w2, b2, *, top_k, num_experts, act):
    """Pure dropless MoE MLP on flat tokens: route -> grouped expert
    FFN -> combine.  Returns (y [N, D], aux scalar, counts [E])."""
    from ..distributed.auto_parallel import moe_dispatch as md
    from ..ops import pallas_grouped as pg
    from ..ops.pallas_gate import pallas_enabled
    from ..ops.pallas_tiles import _demote_f64

    x, rw, w1, b1, w2, b2 = _demote_f64(x, rw, w1, b1, w2, b2)
    N = x.shape[0]
    logits = jnp.dot(x.astype(jnp.float32), rw.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)             # [N, E] f32
    topv, topi = jax.lax.top_k(probs, top_k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)  # renormalize

    bm, nb, rows_total = pg.grouped_layout(N * top_k, num_experts,
                                           x.dtype)
    rows, gid, counts = md.dropless_plan(topi, num_experts, bm, nb)
    xd = md.dropless_dispatch(x, rows, top_k, rows_total)

    gmm = pg.grouped_linear_act if pallas_enabled("grouped_matmul") \
        else pg.grouped_linear_act_ref
    h = gmm(xd, w1, b1, block_group=gid, act=act)
    y_rows = gmm(h, w2, b2, block_group=gid, act="none")
    y = md.dropless_combine(y_rows, rows, topv)

    # Switch-style load balance: E * sum_e(frac_tokens_e * mean_prob_e)
    frac = counts.astype(jnp.float32) / max(N * top_k, 1)
    aux = num_experts * jnp.sum(frac * jnp.mean(probs, axis=0))
    return y.astype(x.dtype), aux, counts


def _moe_mlp_impl(x, rw, w1, b1, w2, b2, *, top_k, num_experts, act):
    y, aux, _ = _moe_mlp_compute(x, rw, w1, b1, w2, b2, top_k=top_k,
                                 num_experts=num_experts, act=act)
    return y, aux


def _make_ep_impl(mesh, axis):
    """Dropless MoE MLP with the stacked experts sharded over ``axis``:
    routing runs globally (tokens replicated), and each device computes
    only its experts' grouped blocks inside a shard_map island.

    Per-device grouped buffers are planned globally: assignments owned
    by other devices route to the device's null group (clamped to the
    kernel's zero expert), so every buffer has static shape and the
    scatter stays exact.  Bitwise, each assignment's expert FFN is the
    same per-block full-K dot as the unsharded path.
    """
    from jax.sharding import PartitionSpec as P

    ep = int(mesh.shape[axis])

    def impl(x, rw, w1, b1, w2, b2, *, top_k, num_experts, act):
        from ..distributed.auto_parallel import moe_dispatch as md
        from ..ops import pallas_grouped as pg
        from ..ops.pallas_gate import pallas_enabled
        from ..ops.pallas_tiles import (_demote_f64, group_segments,
                                        num_group_blocks)

        x, rw, w1, b1, w2, b2 = _demote_f64(x, rw, w1, b1, w2, b2)
        e_loc = num_experts // ep
        N = x.shape[0]
        T = N * top_k
        logits = jnp.dot(x.astype(jnp.float32), rw.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(probs, top_k)
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
        e_flat = topi.reshape(-1).astype(jnp.int32)
        counts = jnp.zeros((num_experts,), jnp.int32).at[e_flat].add(1)

        bm = pg.grouped_block_rows(T, num_experts, x.dtype)
        # +1 group: each device's buffer carries a null group holding
        # the assignments other devices own
        nb = num_group_blocks(T, e_loc + 1, bm)
        xds, gids, row_maps = [], [], []
        for p in range(ep):
            in_p = (e_flat // e_loc) == p
            e_sub = jnp.where(in_p, e_flat - p * e_loc, e_loc)
            csub = jnp.zeros((e_loc + 1,), jnp.int32).at[e_sub].add(1)
            gid, offs = group_segments(csub, bm, nb)
            order = jnp.argsort(e_sub, stable=True)
            csum = jnp.cumsum(csub) - csub
            rank = jnp.arange(T, dtype=jnp.int32) - csum[e_sub[order]]
            rows = jnp.zeros((T,), jnp.int32).at[order].set(
                offs[e_sub[order]] + rank)
            xds.append(md.dropless_dispatch(x, rows, top_k, nb * bm))
            # dummy + tail groups both clamp to the kernel's zero expert
            gids.append(jnp.minimum(gid, e_loc))
            row_maps.append(rows)
        xd = jnp.stack(xds)                     # [P, rows_p, D]
        gid = jnp.stack(gids)                   # [P, nb]
        rows_stack = jnp.stack(row_maps)        # [P, T]

        gmm = pg.grouped_linear_act \
            if pallas_enabled("grouped_matmul", manual=True) \
            else pg.grouped_linear_act_ref

        def island(xd_l, gid_l, w1_l, b1_l, w2_l, b2_l):
            h = gmm(xd_l[0], w1_l, b1_l, block_group=gid_l[0], act=act)
            y = gmm(h, w2_l, b2_l, block_group=gid_l[0], act="none")
            return y[None]

        espec = P(axis)
        y_all = jax.shard_map(
            island, mesh=mesh,
            in_specs=(espec, espec, espec, espec, espec, espec),
            out_specs=espec, check_vma=False)(xd, gid, w1, b1, w2, b2)   # [P, rows_p, D]

        dev = e_flat // e_loc                            # [T]
        y_rows = y_all[dev, rows_stack[dev, jnp.arange(T)]]  # [T, D]
        y = jnp.einsum("nk,nkd->nd", topv,
                       y_rows.reshape(N, top_k, -1).astype(jnp.float32)
                       ).astype(x.dtype)
        frac = counts.astype(jnp.float32) / max(T, 1)
        aux = num_experts * jnp.sum(frac * jnp.mean(probs, axis=0))
        return y, aux

    return impl


class MoEMLP(nn.Layer):
    """Dropless top-k mixture-of-experts FFN with stacked parameters."""

    def __init__(self, cfg: MoEGPTConfig):
        super().__init__()
        H, Iv, E = (cfg.hidden_size, cfg.intermediate_size,
                    cfg.num_experts)
        self.num_experts = E
        self.top_k = cfg.top_k
        self.router = self.create_parameter(
            shape=[H, E], default_initializer=I.XavierNormal())
        self.w1 = self.create_parameter(
            shape=[E, H, Iv], default_initializer=I.XavierNormal())
        self.b1 = self.create_parameter(
            shape=[E, Iv], is_bias=True,
            default_initializer=I.Constant(0.0))
        self.w2 = self.create_parameter(
            shape=[E, Iv, H], default_initializer=I.XavierNormal())
        self.b2 = self.create_parameter(
            shape=[E, H], is_bias=True,
            default_initializer=I.Constant(0.0))
        self.aux_loss = None
        self._ep_impl = None
        self._ep_mesh = None

    def _impl_for_mesh(self):
        """Dense impl, or the ep-sharded island when the global mesh
        carries an expert axis that divides the expert count (the
        `MoELayer._maybe_ep_engine` discipline — re-evaluated whenever
        the mesh changes, so elastic shrink to ep=1 falls back)."""
        from ..distributed.env import global_mesh
        mesh = global_mesh()
        if mesh is self._ep_mesh and self._ep_impl is not None:
            return self._ep_impl
        impl = _moe_mlp_impl
        if mesh is not None:
            for cand in ("ep", "expert"):
                if (cand in mesh.axis_names and mesh.shape[cand] > 1
                        and self.num_experts % mesh.shape[cand] == 0):
                    impl = _make_ep_impl(mesh, cand)
                    break
        self._ep_mesh = mesh
        self._ep_impl = impl
        return impl

    def forward(self, x):
        from ..core.dispatch import dispatch
        orig_shape = list(x.shape)
        N = 1
        for s in orig_shape[:-1]:
            N *= s
        xf = paddle.reshape(x, [N, orig_shape[-1]])
        impl = self._impl_for_mesh()
        y, aux = dispatch(
            "moe_mlp_dropless", impl,
            (xf, self.router, self.w1, self.b1, self.w2, self.b2),
            dict(top_k=self.top_k, num_experts=self.num_experts,
                 act="gelu_tanh"))
        self.aux_loss = aux
        return paddle.reshape(y, orig_shape)


class MoEGPTBlock(nn.Layer):
    def __init__(self, cfg: MoEGPTConfig):
        super().__init__()
        self.ln_1 = nn.LayerNorm(cfg.hidden_size)
        self.attn = GPTAttention(cfg)
        self.ln_2 = nn.LayerNorm(cfg.hidden_size)
        self.mlp = MoEMLP(cfg)
        self.dropout = nn.Dropout(cfg.hidden_dropout_prob)

    def forward(self, x, cache=None, use_cache=False):
        if use_cache:
            a, new_cache = self.attn(self.ln_1(x), cache, True)
            x = x + self.dropout(a)
            x = x + self.dropout(self.mlp(self.ln_2(x)))
            return x, new_cache
        x = x + self.dropout(self.attn(self.ln_1(x), cache))
        x = x + self.dropout(self.mlp(self.ln_2(x)))
        return x


class MoEGPTModel(nn.Layer):
    def __init__(self, cfg: MoEGPTConfig):
        super().__init__()
        self.config = cfg
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = nn.Embedding(cfg.max_position_embeddings,
                                cfg.hidden_size)
        self.h = nn.LayerList([MoEGPTBlock(cfg)
                               for _ in range(cfg.num_hidden_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size)
        self._recompute = cfg.use_recompute

    def forward(self, input_ids, cache=None, use_cache=False):
        b, s = input_ids.shape
        if cache is not None and getattr(cache, "position_ids", None) \
                is not None:
            pos = cache.position_ids
        else:
            past = 0 if cache is None else cache[0][0].shape[1]
            pos = paddle.arange(past, past + s, dtype="int64")
        x = self.wte(input_ids) + self.wpe(pos)
        from ..memory.guard import remat_enabled
        use_remat = self._recompute or remat_enabled()
        new_caches = []
        for i, blk in enumerate(self.h):
            layer_cache = None if cache is None else cache[i]
            if use_cache:
                x, c = blk(x, layer_cache, True)
                new_caches.append(c)
            elif use_remat and layer_cache is None:
                from ..distributed.fleet.recompute import recompute
                x = recompute(blk, x)
            else:
                x = blk(x, layer_cache)
        x = self.ln_f(x)
        if use_cache:
            return x, new_caches
        return x

    def aux_loss(self):
        """Sum of the blocks' router load-balance losses (None before
        the first forward)."""
        losses = [blk.mlp.aux_loss for blk in self.h
                  if blk.mlp.aux_loss is not None]
        if not losses:
            return None
        total = losses[0]
        for aux in losses[1:]:
            total = total + aux
        return total


class MoEGPTForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, cfg: MoEGPTConfig):
        super().__init__()
        self.config = cfg
        self.gpt = MoEGPTModel(cfg)
        if cfg.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     bias_attr=False)

    def cache_spec(self):
        return paged_kv_spec(self.config)

    def forward(self, input_ids, cache=None, use_cache=False):
        if use_cache:
            hidden, new_cache = self.gpt(input_ids, cache, True)
        else:
            hidden = self.gpt(input_ids, cache)
            new_cache = None
        if self.lm_head is not None:
            logits = self.lm_head(hidden)
        else:
            logits = paddle.matmul(hidden, self.gpt.wte.weight,
                                   transpose_y=True)
        if use_cache:
            return logits, new_cache
        return logits

    def aux_loss(self):
        return self.gpt.aux_loss()


class MoEGPTPretrainingCriterion(GPTPretrainingCriterion):
    """Shifted LM loss + weighted router load-balance auxiliary."""

    def __init__(self, model=None, aux_weight=None):
        super().__init__()
        self.model = model
        self.aux_weight = aux_weight

    def forward(self, logits, labels):
        loss = super().forward(logits, labels)
        if self.model is not None:
            aux = self.model.aux_loss()
            if aux is not None:
                w = self.aux_weight
                if w is None:
                    w = getattr(self.model.config, "router_aux_weight",
                                0.01)
                loss = loss + w * aux
        return loss
