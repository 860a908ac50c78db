"""Host-RAM KV tier: the bounded block ring HBM evictions spill into.

The paged pool's refcount-0 LRU (kv_cache.py) parks freed-but-indexed
prefix blocks in HBM until the free list runs dry; beyond that point an
eviction used to delete the prefix for good.  With tiering on, the
evicted block's bytes are *demoted* into this host-RAM ring instead —
per-layer pinned numpy arrays sized by ``PADDLE_TPU_KV_HOST_BUDGET`` —
and the chain-hash entry follows them, so a later prefix hit *promotes*
the block back with one ``device_put`` instead of a re-prefill.  The
effective prefix cache becomes host-RAM sized.

This module owns the dumb storage and the DMA bookkeeping; all policy
(which hash lives where, LRU order, pinning, the commit-generation
stale guard) stays in :class:`~.kv_cache.PagedKVCache`.  Transfers are
dispatched as device gathers/scatters first and admitted into the
PR-4 in-flight pipeline window (``core.pipeline.get_window``), so
outstanding DMA is bounded by the same ``PADDLE_TPU_PIPELINE_DEPTH``
that bounds compute steps; each transfer records a ``kv:dma`` timeline
span and a ``serving.kv_dma_ms`` histogram sample.

Int8 pools carry their per-slot f32 dequant scale tables alongside the
block data — a promoted block with stale scales would dequantize to
garbage, so scales ride every spill/promote/export/import.

:class:`HandoffPayload` reuses the same host representation for the
prefill→decode ownership transfer of the disaggregated engine
(serving/disagg.py): a finished prefill exports its blocks to host
bytes, the decode pool imports them block-granularly, and blocks the
decode pool already holds (prefix hits) are skipped instead of copied.

Knobs: ``PADDLE_TPU_KV_TIERING`` (default on; "0"/"off" disables) and
``PADDLE_TPU_KV_HOST_BUDGET`` (bytes, or "512M"/"2G" form; the ring is
``budget // bytes_per_block`` slots).  The ring registers with the
memory guard as a *host*-side line item (named
``"<pool resident> host tier"``) so triage sees it next to the HBM
charge without it counting against the device budget.
"""
from __future__ import annotations

import os
import time

import numpy as np

from ... import observability as obs

__all__ = ["ENV_KV_TIERING", "ENV_KV_HOST_BUDGET", "kv_tiering_enabled",
           "kv_host_budget", "HostKVPool", "HandoffPayload"]

ENV_KV_TIERING = "PADDLE_TPU_KV_TIERING"
ENV_KV_HOST_BUDGET = "PADDLE_TPU_KV_HOST_BUDGET"


def kv_tiering_enabled():
    """Whether HBM→host spill is allowed (PADDLE_TPU_KV_TIERING,
    default "1"; "0"/"false"/"off" disable).  The tier only actually
    materializes when a host budget resolves to >= 1 block slot."""
    return os.environ.get(ENV_KV_TIERING, "1").lower() not in (
        "0", "false", "off")


def _parse_bytes(v):
    s = str(v).strip()
    if not s:
        return None
    mult = 1
    suffix = s[-1].upper()
    if suffix in ("K", "M", "G", "T"):
        mult = 1024 ** ("KMGT".index(suffix) + 1)
        s = s[:-1]
    try:
        return int(float(s) * mult)
    except ValueError:
        return None


def kv_host_budget():
    """Host-RAM byte budget for the spill ring
    (PADDLE_TPU_KV_HOST_BUDGET, bytes or 512M/2G form; None = unset)."""
    return _parse_bytes(os.environ.get(ENV_KV_HOST_BUDGET, ""))


def _dma_span(direction, nbytes, **attrs):
    """One ``kv:dma`` timeline span (the transfer-latency lane)."""
    return obs.span("kv:dma", cat="dma", dir=direction,
                    bytes=int(nbytes), **attrs)


def _observe_dma(direction, nbytes, elapsed_s):
    reg = obs.get_registry()
    reg.histogram("serving.kv_dma_ms").observe(elapsed_s * 1e3)
    reg.counter(f"serving.kv_dma_{direction}_bytes").inc(int(nbytes))


class HandoffPayload:
    """One sequence's paged KV state as host bytes: per-layer stacked
    block data ``[nb, bs, H * D]`` (blocks as they lie in the pool:
    ``kv_cache.py``'s one format; + scale tables ``[nb, bs, lanes]``
    for int8 pools) in table order.  Produced by
    ``PagedKVCache.export_sequence`` and consumed block-granularly by
    ``import_sequence`` on another pool."""

    __slots__ = ("k", "v", "k_scales", "v_scales", "num_blocks",
                 "block_size", "kv_dtype", "nbytes")

    def __init__(self, k, v, k_scales, v_scales, block_size, kv_dtype):
        self.k = k                    # [layers] of [nb, bs, H * D]
        self.v = v
        self.k_scales = k_scales      # [layers] of [nb, bs, lanes]|None
        self.v_scales = v_scales
        self.num_blocks = int(k[0].shape[0]) if k else 0
        self.block_size = int(block_size)
        self.kv_dtype = str(kv_dtype)
        self.nbytes = sum(int(a.nbytes) for a in k) \
            + sum(int(a.nbytes) for a in v) \
            + sum(int(a.nbytes) for a in (k_scales or ())) \
            + sum(int(a.nbytes) for a in (v_scales or ()))

    def __repr__(self):
        return (f"HandoffPayload(blocks={self.num_blocks}, "
                f"dtype={self.kv_dtype}, {self.nbytes} bytes)")


class HostKVPool:
    """The bounded pinned ring: ``num_slots`` host block slots, each a
    full cross-layer K/V block (+ scales).  Pure storage — a free list
    and preallocated C-contiguous numpy arrays; eviction policy lives
    in the paged cache that owns this ring."""

    def __init__(self, num_layers, num_heads, block_size, head_dim,
                 np_dtype, scale_lanes, num_slots):
        self.num_layers = int(num_layers)
        self.block_size = int(block_size)
        self.scale_lanes = int(scale_lanes)
        self.num_slots = int(num_slots)
        # a slot holds a block as it lies in the pool
        shape = (self.num_slots, self.block_size,
                 int(num_heads) * int(head_dim))
        # one pinned (preallocated, reused in place) array per layer
        # per side; slots are recycled through the free list, so the
        # ring never grows past the budget
        self._k = [np.zeros(shape, np_dtype)
                   for _ in range(self.num_layers)]
        self._v = [np.zeros(shape, np_dtype)
                   for _ in range(self.num_layers)]
        if self.scale_lanes:
            sshape = (self.num_slots, self.block_size, self.scale_lanes)
            self._ks = [np.zeros(sshape, np.float32)
                        for _ in range(self.num_layers)]
            self._vs = [np.zeros(sshape, np.float32)
                        for _ in range(self.num_layers)]
        else:
            self._ks = self._vs = None
        self._free = list(range(self.num_slots - 1, -1, -1))

    @property
    def nbytes(self):
        n = sum(a.nbytes for a in self._k) + sum(a.nbytes for a in self._v)
        if self._ks is not None:
            n += sum(a.nbytes for a in self._ks)
            n += sum(a.nbytes for a in self._vs)
        return int(n)

    @property
    def free_slots(self):
        return len(self._free)

    @property
    def used_slots(self):
        return self.num_slots - len(self._free)

    def take(self):
        """A free slot, or None when the ring is full (the owner must
        evict one of its LRU entries first)."""
        return self._free.pop() if self._free else None

    def give(self, slot):
        self._free.append(int(slot))

    def write(self, slot, k_parts, v_parts, ks_parts=None,
              vs_parts=None):
        """Land one block's host bytes: per-layer [bs, H * D] arrays
        (+ [bs, lanes] scales) copied into the pinned ring slot."""
        for i in range(self.num_layers):
            np.copyto(self._k[i][slot], k_parts[i], casting="no")
            np.copyto(self._v[i][slot], v_parts[i], casting="no")
        if self._ks is not None:
            for i in range(self.num_layers):
                np.copyto(self._ks[i][slot], ks_parts[i], casting="no")
                np.copyto(self._vs[i][slot], vs_parts[i], casting="no")

    def read(self, slot):
        """(k_parts, v_parts, ks_parts, vs_parts) views of one slot."""
        k = [self._k[i][slot] for i in range(self.num_layers)]
        v = [self._v[i][slot] for i in range(self.num_layers)]
        if self._ks is None:
            return k, v, None, None
        return (k, v, [self._ks[i][slot] for i in range(self.num_layers)],
                [self._vs[i][slot] for i in range(self.num_layers)])

    def __repr__(self):
        return (f"HostKVPool(slots={self.used_slots}/{self.num_slots}, "
                f"layers={self.num_layers}, {self.nbytes} bytes)")
