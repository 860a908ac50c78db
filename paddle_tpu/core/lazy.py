"""Lazy eager execution: the auto-trace tier for dygraph.

Reference parity: Paddle's dygraph hides per-op latency with generated
C++ paths and async CUDA launches (`paddle/fluid/eager/`,
SURVEY.md §3.1: per-op dispatch is THE dygraph bottleneck) [UNVERIFIED —
empty reference mount].  On TPU the equivalent lever is SURVEY.md §7's
"dygraph without per-op sync": eager ops build lazy expressions and
flush to ONE cached compiled segment at sync points — `.numpy()`,
`float()`, control flow on values, anything that truly needs data.

How a train step executes under lazy mode:
  * forward ops append ``LazyNode``s; outputs are ``LazyValue``s whose
    shape/dtype come from ``jax.eval_shape`` (InferMeta's role) — no
    device dispatch happens;
  * ops that need autograd record their VJP residuals as EXTRA lazy
    outputs (``jax.vjp``'s returned function is a pytree of residual
    arrays + static structure, captured abstractly at record time), so
    ``loss.backward()``'s tape walk records backward nodes into the SAME
    buffer — forward and backward become one graph;
  * the fused optimizer step consumes grads lazily too, so the whole
    train step — forward, backward, parameter update — flushes as ONE
    jitted, fingerprint-keyed segment at the first host read.  Steady
    state: 1–2 executable launches per step instead of hundreds of
    per-op round trips.

Fingerprinted reuse: a segment's structural fingerprint (interned
per-node op keys + wiring + leaf avals incl. weak-typedness + the
donation mask) keys a bounded LRU of AOT-compiled executables
(`TracedFunction`-style), so the second execution of a training-loop
body is a pure cache hit — zero retrace, zero relower.  Python scalars
are hoisted to weak-typed traced leaves by the dispatcher
(core/dispatch.py) so loop counters don't bake into the fingerprint.

Flush triggers: host reads (``__jax_array__``/``__array__``/``force``),
value-dependent control flow (``float()``/``bool()`` on a Tensor), and
the op-count watermark ``PADDLE_TPU_LAZY_MAX_NODES`` (re-read at every
``enable_lazy()``).

In-place param updates donate their old buffers: when a Tensor's buffer
is rebound to a pending LazyValue (optimizer ``p._inplace_update``),
the replaced concrete array is noted and — if nothing outside the
segment still references it at flush time — passed to XLA as a donated
argument, so params/opt-state cost 1x HBM in the replayed step (gated
on ``FLAGS_buffer_donation``; the donation mask is part of the
fingerprint).

Observability: a flush is three boundary spans (timeline behind the
gate, and always the profiler's clock): ``lazy:wire`` (wiring, masks,
the key's hash), ``lazy:flush`` (``cat="dispatch"``, attrs: nodes,
cache_hit, fingerprint — the replay's dispatch) and ``lazy:writeback``;
segment compiles run under ``compile:lazy:segment``.  ``record_node``
has none: per-node paths stay free.  The metrics registry carries
``eager.segment_cache_hit_rate`` / ``eager.segment_cache_evictions``,
and ``phase_breakdown()`` exposes the lazy lane.  Fresh executables go
through the memory-guard preflight before their first dispatch, so
segments are held to the HBM budget like every other compiled program.

Enablement is PROCESS-global (``enable_lazy`` / ``PADDLE_TPU_LAZY=1`` /
``paddle.incubate.lazy_eager()``); each thread records into its own
buffer, and forcing a value flushes the buffer that owns it, so a
tensor produced on one thread may be read from another (checkpoint /
logging threads).
"""
from __future__ import annotations

import os
import threading
from collections import Counter, OrderedDict, deque

import numpy as np
import jax
import jax.numpy as jnp

from ..observability.timeline import (enabled as _obs_enabled,
                                      span as _span)

__all__ = ["LazyValue", "lazy_enabled", "enable_lazy", "lazy_guard",
           "flush", "concrete"]


class _Buffer:
    """One thread's pending segment."""

    __slots__ = ("pending", "flushing", "lock", "donate")

    def __init__(self):
        self.pending = []
        self.flushing = False
        self.lock = threading.RLock()
        # id(old array) -> old array for buffers an _inplace_update
        # replaced with a pending LazyValue (donation candidates); the
        # strong ref keeps the id stable until the flush decides
        self.donate = {}


class _ThreadState(threading.local):
    def __init__(self):
        self.buffer = _Buffer()


_tls = _ThreadState()

# process-global switch (fast path: a plain module attribute read)
_ENABLED = False
# sticky: once lazy has EVER been on, fallback paths must concretize
_EVER_ENABLED = False

# segment executable LRU: fingerprint key -> compiled AOT executable.
# Bounded like TracedFunction._cache; hits move to the back, inserts
# past the cap evict the least-recently-replayed segment.
_segment_cache: OrderedDict = OrderedDict()
_SEGMENT_CACHE_MAX = 512
# capture statistics (read by jit/sot.py reports, chip_smoke.py and
# benchmarks/runners/train_dygraph.py):
# monotonic counters
stats = {"flushes": 0, "cache_hits": 0, "compiles": 0, "nodes": 0,
         "evictions": 0, "donated": 0}
# per-op abstract-eval cache (also memoizes each op's replay `run`
# callable, so a steady-state dispatch allocates no new closures)
_abseval_cache: dict = {}
_ABSEVAL_CACHE_MAX = 8192


def _max_nodes_env(default=4096):
    try:
        return int(os.environ.get("PADDLE_TPU_LAZY_MAX_NODES", default))
    except (TypeError, ValueError):
        return default


# auto-flush watermark: a loop that never reads values must not grow
# the buffer without limit (PADDLE_TPU_LAZY_MAX_NODES, re-read at every
# enable_lazy so tests/jobs can retune without a restart)
_AUTO_FLUSH_NODES = _max_nodes_env()


def lazy_enabled():
    return _ENABLED and not _tls.buffer.flushing


def enable_lazy(on=True):
    """Switch lazy eager mode process-wide.  Returns previous mode."""
    global _ENABLED, _EVER_ENABLED, _AUTO_FLUSH_NODES
    prev = _ENABLED
    if prev and not on:
        flush()
    _ENABLED = bool(on)
    _EVER_ENABLED = _EVER_ENABLED or _ENABLED
    if on and "PADDLE_TPU_LAZY_MAX_NODES" in os.environ:
        # env knob re-read on every enable so jobs/tests can retune the
        # watermark without a process restart; a directly-assigned
        # module value (tests) is left alone when the env is unset
        _AUTO_FLUSH_NODES = _max_nodes_env(_AUTO_FLUSH_NODES)
    return prev


class lazy_guard:
    """Context manager: run a block in lazy eager mode."""

    def __init__(self, on=True):
        self.on = on

    def __enter__(self):
        self.prev = enable_lazy(self.on)
        return self

    def __exit__(self, *exc):
        enable_lazy(self.prev)
        return False


def _force_delegate(op):
    def fn(self, *args, **kwargs):
        return getattr(self.force(), op)(*args, **kwargs)
    fn.__name__ = op
    return fn


class LazyValue:
    """A deferred array: aval now, data after its segment flushes.

    Real data uses flush transparently: jnp/numpy conversion via
    ``__jax_array__``/``__array__``, unknown attributes (``.at``,
    ``.sharding``, ``.reshape`` …) via ``__getattr__``, and arithmetic
    dunders by force-and-delegate.  ``__add__`` alone stays lazy — it is
    the cotangent-accumulation path of the tape walk."""

    __slots__ = ("aval", "node", "out_index", "_concrete", "_error")

    def __init__(self, aval, node, out_index):
        self.aval = aval
        self.node = node
        self.out_index = out_index
        self._concrete = None
        self._error = None

    # ---- aval surface (keeps .shape/.dtype users working unforced) ----
    @property
    def shape(self):
        return self.aval.shape

    @property
    def dtype(self):
        return self.aval.dtype

    @property
    def ndim(self):
        return len(self.aval.shape)

    @property
    def size(self):
        return int(np.prod(self.aval.shape)) if self.aval.shape else 1

    def force(self):
        if self._concrete is None:
            if self._error is not None:
                raise RuntimeError(
                    "this lazy value's segment failed to execute"
                ) from self._error
            self.node.buffer_flush()
            if self._concrete is None:
                if self._error is not None:
                    raise RuntimeError(
                        "this lazy value's segment failed to execute"
                    ) from self._error
                raise RuntimeError(
                    "lazy value did not materialize on flush")
        return self._concrete

    # jax/numpy interop: any real data use flushes transparently
    def __jax_array__(self):
        return self.force()

    def __array__(self, dtype=None):
        a = np.asarray(self.force())
        return a.astype(dtype) if dtype is not None else a

    def block_until_ready(self):
        self.force().block_until_ready()
        return self

    def __getattr__(self, name):
        # anything beyond the lazy surface (.at, .sharding, .devices,
        # .reshape, .astype …) forces and delegates to the real array
        if name.startswith("__"):
            raise AttributeError(name)
        return getattr(self.force(), name)

    def __add__(self, other):
        return lazy_add(self, other)

    def __radd__(self, other):
        return lazy_add(other, self)

    # force-and-delegate arithmetic for non-core consumers of ._value
    __sub__ = _force_delegate("__sub__")
    __rsub__ = _force_delegate("__rsub__")
    __mul__ = _force_delegate("__mul__")
    __rmul__ = _force_delegate("__rmul__")
    __truediv__ = _force_delegate("__truediv__")
    __rtruediv__ = _force_delegate("__rtruediv__")
    __pow__ = _force_delegate("__pow__")
    __neg__ = _force_delegate("__neg__")
    __matmul__ = _force_delegate("__matmul__")
    __getitem__ = _force_delegate("__getitem__")

    def __repr__(self):
        st = "pending" if self._concrete is None else "ready"
        return f"LazyValue({self.aval.shape}, {self.aval.dtype}, {st})"


class LazyNode:
    __slots__ = ("run", "inputs", "outs", "key", "buffer", "label",
                 "raw_key")

    def __init__(self, run, inputs, avals, key, buffer, label, raw_key):
        self.run = run                 # run(*input_vals) -> tuple
        self.inputs = list(inputs)     # LazyValue | concrete array
        self.key = key                 # interned int (fingerprint atom)
        self.buffer = buffer
        self.label = label             # op name, for TPU205 naming
        self.raw_key = raw_key         # structural key, for TPU205 diff
        self.outs = [LazyValue(a, self, i) for i, a in enumerate(avals)]

    def buffer_flush(self):
        buf = self.buffer
        if buf is not None:
            _flush_buffer(buf)


_aval_intern: dict = {}


def _aval_of(v):
    """ShapeDtypeStruct for one dispatch operand, interned by
    (shape, dtype, weak_type): the lazy recorder abstractifies every
    operand of every recorded op, and a training loop re-sees the same
    handful of signatures millions of times (the lenet eager-dispatch
    triage).  weak_type rides along because hoisted python scalars must
    keep python-number promotion inside the replayed program."""
    if isinstance(v, LazyValue):
        sig = (v.aval.shape, v.aval.dtype,
               bool(getattr(v.aval, "weak_type", False)))
    else:
        sig = (jnp.shape(v), jnp.result_type(v), _weak_of(v))
    aval = _aval_intern.get(sig)
    if aval is None:
        if len(_aval_intern) >= 4096:
            return jax.ShapeDtypeStruct(sig[0], sig[1], weak_type=sig[2])
        aval = _aval_intern[sig] = jax.ShapeDtypeStruct(
            sig[0], sig[1], weak_type=sig[2])
    return aval


def _weak_of(v):
    """Is ``v`` weakly typed for promotion purposes?  jax arrays carry
    the flag; bare python numbers ARE weak."""
    w = getattr(v, "weak_type", None)
    if w is not None:
        return bool(w)
    return isinstance(v, (bool, int, float, complex))


_key_intern: dict = {}
_intern_lock = threading.Lock()


def _intern_key(key):
    """Big structural op keys hash O(size) on every dict lookup; the
    segment wiring key contains one per node per flush, so nodes carry
    a small interned int instead.  Locked: a get-then-set race could
    hand one int to two different keys — wrong-replay territory."""
    i = _key_intern.get(key)
    if i is None:
        with _intern_lock:
            i = _key_intern.setdefault(key, len(_key_intern))
    return i


def record_node(run, inputs, out_avals, key, label="op", raw_key=None):
    """Append one node to this thread's buffer; returns its outputs.
    ``key`` may be pre-interned (int) or a structural tuple."""
    buf = _tls.buffer
    if len(buf.pending) >= _AUTO_FLUSH_NODES:
        # flush BEFORE appending: the new node's outputs have no Tensor
        # wrapper yet, so the liveness pruning would see them as dead
        _flush_buffer(buf)
    kid = key if isinstance(key, int) else _intern_key(key)
    node = LazyNode(run, inputs, out_avals, kid, buf, label,
                    raw_key if raw_key is not None else key)
    with buf.lock:  # another thread may be force-flushing this buffer
        buf.pending.append(node)
    return node.outs


def lazy_add(a, b):
    """Cotangent-accumulation add that stays lazy when either side is."""
    la, lb = isinstance(a, LazyValue), isinstance(b, LazyValue)
    if la and a._concrete is not None:
        a, la = a._concrete, False
    if lb and b._concrete is not None:
        b, lb = b._concrete, False
    if not (la or lb) or not lazy_enabled():
        a = a.force() if la else a
        b = b.force() if lb else b
        return a + b
    aa, ab = _aval_of(a), _aval_of(b)
    out = jax.eval_shape(jnp.add, aa, ab)
    key = ("lazy_add", aa.shape, str(aa.dtype), ab.shape, str(ab.dtype))
    return record_node(lambda x, y: (jnp.add(x, y),), [a, b],
                       [out], key, label="lazy_add")[0]


def note_donation(old, new):
    """Called by ``Tensor._inplace_update``: when a concrete buffer is
    replaced by a pending LazyValue (optimizer in-place param update),
    the old array becomes a donation candidate for this thread's next
    flush.  A forced LazyValue (last step's segment output — the steady
    state) donates its materialized array."""
    if not (isinstance(new, LazyValue) and new._concrete is None):
        return
    if isinstance(old, LazyValue):
        old = old._concrete
        if old is None:
            return
    if isinstance(old, jax.Array) and not isinstance(old,
                                                     jax.core.Tracer):
        _tls.buffer.donate[id(old)] = old


def concrete(v):
    """Force if lazy; identity otherwise."""
    return v.force() if isinstance(v, LazyValue) else v


def concrete_values(tensors):
    """``tuple(t._value, forced)`` — THE compiled-call boundary helper:
    a pending LazyValue handed to a lowered executable (or jit.lower)
    raises 'Triggering __jax_array__ during abstractification', so
    every site that feeds raw tensor buffers into compiled code goes
    through here."""
    return tuple(concrete(t._value) for t in tensors)


def flush():
    """Flush this thread's pending segment."""
    _flush_buffer(_tls.buffer)


def _flush_buffer(buf):
    with buf.lock:
        pending, buf.pending = buf.pending, []
        donate, buf.donate = buf.donate, {}
        if not pending:
            return
        buf.flushing = True
        try:
            _flush_nodes(pending, donate)
        except BaseException as e:
            # every in-flight value of this segment can never
            # materialize; remember the cause so later reads point at
            # the real error instead of a bare "did not materialize"
            for n in pending:
                for lv in n.outs:
                    if lv._concrete is None:
                        lv._error = e
            raise
        finally:
            buf.flushing = False


def _liveness_masks(pending):
    """Per-node tuple of bools: which outputs are referenced OUTSIDE the
    segment (a Tensor's ``_value``, a vjp closure's residual, another
    thread) and must therefore materialize.  Everything else stays
    INTERNAL to the replay program so XLA can fuse, DCE and reuse its
    buffers — returning every intermediate (activations, grads, adam
    temporaries) as a program output forbids all buffer reuse and was a
    10x+ step-time hit at GPT scale.

    Accounting: ``sys.getrefcount(lv)`` counts (1) the getrefcount arg,
    (2) the local binding, (3) the ``node.outs`` entry, plus one per
    in-segment consumer input — anything beyond that is external.
    Hidden references (objects kept alive in cycles, C-level containers)
    only OVERcount, i.e. materialize more than strictly needed — never
    the silent-drop direction; a genuinely-referenced value misjudged
    dead would fail LOUDLY at force() ("did not materialize")."""
    import sys
    # generator scope: no leaked local binding to skew the refcounts
    in_seg = Counter(id(v) for n in pending for v in n.inputs
                     if isinstance(v, LazyValue))
    masks = []
    for n in pending:
        m = []
        for i in range(len(n.outs)):
            lv = n.outs[i]
            ext = sys.getrefcount(lv) - 3 - in_seg.get(id(lv), 0)
            m.append(ext > 0)
            del lv
        masks.append(tuple(m))
    return masks


def _donatable_leaves(leaves, pending, donate):
    """Leaf indices safe to donate to XLA: the leaf was noted as an
    in-place-replaced buffer AND nothing outside this flush still
    references it.  Refcount accounting mirrors _liveness_masks: the
    expected count is getrefcount's own arg + the loop binding + the
    ``donate`` map's strong ref + every ``leaves``/``node.inputs``
    occurrence; anything beyond means a user still holds the old
    buffer — overcounting (hidden refs) only SKIPS a donation, never
    donates a live buffer."""
    if not donate:
        return ()
    from ..framework.flags import get_flags
    if not get_flags("FLAGS_buffer_donation")["FLAGS_buffer_donation"]:
        return ()
    import sys
    inputs_ct = Counter(id(v) for n in pending for v in n.inputs
                        if not isinstance(v, LazyValue))
    leaves_ct = Counter(id(v) for v in leaves)
    # a forced LazyValue input holds ONE ref to its materialized array
    # via _concrete.  That ref is creditable only when the LazyValue
    # itself has no references outside these input lists — a tensor
    # still bound to it (detach() alias, user variable) could read the
    # array after the flush, so it must block donation.
    lv_occ = Counter(id(v) for n in pending for v in n.inputs
                     if isinstance(v, LazyValue)
                     and v._concrete is not None)
    lv_credit = Counter()
    seen = set()
    for n in pending:
        for v in n.inputs:
            if not (isinstance(v, LazyValue)
                    and v._concrete is not None):
                continue
            vid = id(v)
            if vid in seen:
                continue
            seen.add(vid)
            # getrefcount arg + loop binding + input-list occurrences
            if sys.getrefcount(v) <= 2 + lv_occ[vid]:
                lv_credit[id(v._concrete)] += 1
    out = []
    for i in range(len(leaves)):
        v = leaves[i]
        vid = id(v)
        if vid not in donate or leaves_ct[vid] != 1:
            # aliased-operand duplicate slots can't donate one buffer
            # twice; keep it simple and keep them all
            del v
            continue
        expected = 3 + leaves_ct[vid] + inputs_ct[vid] + lv_credit[vid]
        if sys.getrefcount(v) <= expected:
            out.append(i)
        del v
    return tuple(out)


class _Segment:
    """One cached AOT-compiled segment executable."""

    __slots__ = ("compiled", "fingerprint", "n_donated")

    def __init__(self, compiled, fingerprint, n_donated):
        self.compiled = compiled
        self.fingerprint = fingerprint
        self.n_donated = n_donated


# segment compile history for the TPU205 thrash audit: every compiled
# fingerprint with its per-node structural keys, grouped by op-name
# sequence so the audit can diff two variants and NAME the node that
# keeps changing (a baked-in python scalar, a drifting shape)
_segment_history: deque = deque(maxlen=256)
_seg_groups: dict = {}          # label tuple -> set of fingerprints
_SEG_GROUPS_MAX = 512
_seg_flagged: set = set()


def _frag_threshold():
    try:
        return int(os.environ.get("PADDLE_TPU_EAGER_FRAG_THRESHOLD",
                                  "16"))
    except (TypeError, ValueError):
        return 16


def _note_segment_compile(fp, pending, leaf_sig):
    labels = tuple(n.label for n in pending)
    _segment_history.append({
        "fingerprint": fp,
        "labels": labels,
        "keys": tuple(n.raw_key for n in pending),
        "leaf_sig": leaf_sig,
    })
    if len(_seg_groups) < _SEG_GROUPS_MAX or labels in _seg_groups:
        group = _seg_groups.setdefault(labels, set())
        group.add(fp)
        if len(group) == _frag_threshold() \
                and labels not in _seg_flagged:
            # live thrash watch, same shape as dispatch._note_cache_insert
            _seg_flagged.add(labels)
            try:
                from ..analysis.diagnostics import record
                from ..analysis.recompile import audit_segment_cache
                for d in audit_segment_cache(only_labels=labels,
                                             threshold=1):
                    record(d)
            except Exception:
                pass


def _metrics_flush_update(hit):
    """Registry lanes (no-ops with observability off)."""
    from ..observability.registry import get_registry
    reg = get_registry()
    if hit:
        reg.counter("eager.segment_cache_hits").inc()
    else:
        reg.counter("eager.segment_cache_misses").inc()
    fl = stats["flushes"]
    if fl:
        reg.gauge("eager.segment_cache_hit_rate").set(
            stats["cache_hits"] / fl)


def _compile_segment(seg_key, pending, wiring, masks, leaves,
                     donate_idx, kept_idx, fp):
    runs = [n.run for n in pending]
    wires = [w for _, w in wiring]
    n_leaves = len(leaves)
    d_idx, k_idx = tuple(donate_idx), tuple(kept_idx)

    def replay(donated, kept):
        leaf_vals = [None] * n_leaves
        for i, v in zip(d_idx, donated):
            leaf_vals[i] = v
        for i, v in zip(k_idx, kept):
            leaf_vals[i] = v
        results = []
        out = []
        for run, slots, mask in zip(runs, wires, masks):
            ins = [results[s[1]][s[2]] if s[0] == "n"
                   else leaf_vals[s[1]] for s in slots]
            res = run(*ins)
            results.append(res)
            out.append(tuple(
                o for o, keep in zip(res, mask) if keep))
        return tuple(out)

    jit_kwargs = {}
    if d_idx:
        jit_kwargs["donate_argnums"] = (0,)
    donated = tuple(leaves[i] for i in d_idx)
    kept = tuple(leaves[i] for i in k_idx)
    with _span("compile:lazy:segment", cat="compile", boundary=True,
               nodes=len(pending), fingerprint=fp):
        compiled = jax.jit(replay, **jit_kwargs) \
            .lower(donated, kept).compile()
    # memory-guard preflight: hold the fresh segment executable to the
    # HBM budget (in-flight leaves + materialized outputs) before its
    # first dispatch, exactly like TracedFunction/Executor programs
    from ..memory.guard import preflight_check
    preflight_check(compiled, program=f"lazy:segment#{fp}")
    return _Segment(compiled, fp, len(d_idx))


def _flush_nodes(pending, donate=None):
    with _span("lazy:wire", boundary=True, nodes=len(pending)):
        seg_key, masks, leaves, leaf_sig, donate_idx, kept_idx, wiring \
            = _wire(pending, donate)
        seg = _segment_cache.get(seg_key)     # hashes the whole key
    stats["flushes"] += 1
    stats["nodes"] += len(pending)
    hit = seg is not None
    if hit:
        stats["cache_hits"] += 1
        _segment_cache.move_to_end(seg_key)
    else:
        stats["compiles"] += 1
        fp = _intern_key(seg_key)
        seg = _compile_segment(seg_key, pending, wiring, masks, leaves,
                               donate_idx, kept_idx, fp)
        _segment_cache[seg_key] = seg
        if len(_segment_cache) > _SEGMENT_CACHE_MAX:
            _segment_cache.popitem(last=False)
            stats["evictions"] += 1
            if _obs_enabled():
                from ..observability.registry import get_registry
                get_registry().counter(
                    "eager.segment_cache_evictions").inc()
        _note_segment_compile(fp, pending, leaf_sig)
    stats["donated"] += len(donate_idx)
    if _obs_enabled():
        _metrics_flush_update(hit)
    donated = tuple(leaves[i] for i in donate_idx)
    kept = tuple(leaves[i] for i in kept_idx)
    del leaves
    from ..device import hbm_oom_context
    with _span("lazy:flush", cat="dispatch", boundary=True,
               nodes=len(pending), cache_hit=hit,
               fingerprint=seg.fingerprint, donated=len(donated)):
        with hbm_oom_context():  # dygraph OOMs surface here
            out = seg.compiled(donated, kept)
    with _span("lazy:writeback", boundary=True):
        for n, vals, mask in zip(pending, out, masks):
            it = iter(vals)
            for lv, keep in zip(n.outs, mask):
                if keep:
                    lv._concrete = next(it)
                    # break the lv -> node -> sibling-outs chain: a
                    # rebound tensor must free (and donate) last step's
                    # buffers, not keep the whole flushed segment alive
                    # transitively
                    lv.node = None
            n.run = None
            n.inputs = []
            n.buffer = None


def _wire(pending, donate):
    """The flush's host work before the segment-cache lookup: operand
    wiring, liveness masks, the donation mask and the segment key."""
    leaves = []
    leaf_pos: dict = {}          # id(array) -> leaf index
    wiring = []
    node_index = {id(n): i for i, n in enumerate(pending)}
    masks = _liveness_masks(pending)

    for n in pending:
        slots = []
        node_leaves = set()      # leaf indices already used by THIS node

        def leaf_slot(v):
            # share leaves ACROSS nodes, but aliased operands of one
            # node must stay distinct jit arguments: the recorded vjp
            # arity came from an abstract probe with per-occurrence
            # tracers, and jax dedupes jaxpr consts by identity — one
            # tracer in two operand slots drops residuals at replay
            k = leaf_pos.get(id(v))
            if k is None or k in node_leaves:
                new = len(leaves)
                leaves.append(v)
                if k is None:
                    leaf_pos[id(v)] = new
                k = new
            node_leaves.add(k)
            return ("l", k)

        for v in n.inputs:
            if isinstance(v, LazyValue) and v._concrete is not None:
                v = v._concrete
            if isinstance(v, LazyValue):
                ni = node_index.get(id(v.node))
                if ni is None:
                    # produced by another thread's (or a failed)
                    # segment: materialize it now
                    slots.append(leaf_slot(v.force()))
                    continue
                slots.append(("n", ni, v.out_index))
            else:
                slots.append(leaf_slot(v))
        wiring.append((n.key, tuple(slots)))

    donate_idx = _donatable_leaves(leaves, pending, donate)
    dset = set(donate_idx)
    kept_idx = tuple(i for i in range(len(leaves)) if i not in dset)
    leaf_sig = tuple(
        (jnp.shape(v), str(jnp.result_type(v)), _weak_of(v))
        for v in leaves)
    seg_key = (tuple(wiring), tuple(masks), leaf_sig, donate_idx)
    return seg_key, masks, leaves, leaf_sig, donate_idx, kept_idx, wiring


# ---------------------------------------------------------------------
# dispatch integration (called from core.dispatch)
# ---------------------------------------------------------------------
def abs_eval(op_key, record, template, tensor_idx, attrs, impl,
             in_avals, n_diff=None):
    """Cached per-op abstract evaluation: output avals; for recorded ops
    also the VJP residual avals + pytree structure (captured via side
    effect during the abstract trace — the structure is static).

    The meta dict also memoizes the node's replay ``run`` callable:
    equal op keys prove behavioral equality (same contract as the
    per-op jit caches), so a steady-state dispatch reuses one closure
    instead of building template/closure objects per call.

    ``n_diff``: how many leading inputs are differentiable Tensor
    operands — hoisted python-scalar leaves ride after them and stay
    out of the VJP (their "gradient" is never consumed)."""
    cache_key = (op_key, bool(record))
    meta = _abseval_cache.get(cache_key)
    if meta is not None:
        return meta

    t_idx = tuple(tensor_idx)
    if n_diff is None:
        n_diff = len(t_idx)
    side = {}

    if not record:
        def probe(*ins):
            full = list(template)
            for i, v in zip(t_idx, ins):
                full[i] = v
            out = impl(*full, **attrs)
            side["is_multi"] = isinstance(out, (tuple, list))
            outs_t = tuple(out) if side["is_multi"] else (out,)
            side["none_mask"] = tuple(o is None for o in outs_t)
            return tuple(o for o in outs_t if o is not None)

        out_avals = jax.eval_shape(probe, *in_avals)
        meta = {"record": False, "out_avals": tuple(out_avals),
                "is_multi": side["is_multi"],
                "none_mask": side["none_mask"]}
    else:
        def probe(*ins):
            hoisted = ins[n_diff:]

            def f(*xs):
                full = list(template)
                for i, v in zip(t_idx, tuple(xs) + tuple(hoisted)):
                    full[i] = v
                return impl(*full, **attrs)

            outs, vjp = jax.vjp(f, *ins[:n_diff])
            res, treedef = jax.tree_util.tree_flatten(vjp)
            side["treedef"] = treedef
            side["is_multi"] = isinstance(outs, (tuple, list))
            side["out_struct"] = jax.tree_util.tree_structure(outs)
            outs_t = tuple(outs) if side["is_multi"] else (outs,)
            side["n_out"] = len(outs_t)
            side["none_mask"] = tuple(o is None for o in outs_t)
            return outs_t + tuple(res)

        all_avals = jax.eval_shape(probe, *in_avals)
        n_out = side["n_out"]
        meta = {"record": True,
                "out_avals": tuple(all_avals[:n_out]),
                "res_avals": tuple(all_avals[n_out:]),
                "treedef": side["treedef"],
                "out_struct": side["out_struct"],
                "is_multi": side["is_multi"],
                "none_mask": side["none_mask"]}
    meta["run"] = make_fwd_run(template, t_idx, attrs, impl, record,
                               n_diff)
    meta["all_avals"] = meta["out_avals"] + \
        tuple(meta.get("res_avals", ()))
    if len(_abseval_cache) < _ABSEVAL_CACHE_MAX:
        _abseval_cache[cache_key] = meta
    return meta


def make_fwd_run(template, tensor_idx, attrs, impl, record,
                 n_diff=None):
    """The node's replay function.  All behavior-affecting state is in
    the node key (op key), so identical keys may share compiled code."""
    t_idx = tuple(tensor_idx)
    if n_diff is None:
        n_diff = len(t_idx)
    if not record:
        def run(*ins):
            full = list(template)
            for i, v in zip(t_idx, ins):
                full[i] = v
            out = impl(*full, **attrs)
            outs_t = tuple(out) if isinstance(out, (tuple, list)) \
                else (out,)
            return tuple(o for o in outs_t if o is not None)
        return run

    def run(*ins):
        hoisted = ins[n_diff:]

        def f(*xs):
            full = list(template)
            for i, v in zip(t_idx, tuple(xs) + tuple(hoisted)):
                full[i] = v
            return impl(*full, **attrs)

        outs, vjp = jax.vjp(f, *ins[:n_diff])
        res, _ = jax.tree_util.tree_flatten(vjp)
        outs_t = tuple(outs) if isinstance(outs, (tuple, list)) \
            else (outs,)
        return outs_t + tuple(res)
    return run


def make_lazy_vjp(op_key, res_values, treedef, out_struct):
    """GradNode.vjp_fn for a lazily recorded op: applying it records a
    backward node into the (same) buffer, so backward defers too."""

    def vjp_fn(cts):
        flat_cts, _ = jax.tree_util.tree_flatten(
            cts, is_leaf=lambda x: isinstance(x, LazyValue))
        n_res = len(res_values)

        ct_sig = tuple((_aval_of(c).shape, str(_aval_of(c).dtype))
                       for c in flat_cts)
        key = ("bwd", op_key, ct_sig)
        meta = _abseval_cache.get(key)
        if meta is None:
            def bwd_run(*ins):
                vjp = jax.tree_util.tree_unflatten(treedef,
                                                   ins[:n_res])
                ct_vals = jax.tree_util.tree_unflatten(
                    out_struct, list(ins[n_res:]))
                return tuple(vjp(ct_vals))

            in_avals = [_aval_of(v) for v in res_values] + \
                [_aval_of(c) for c in flat_cts]
            meta = {"avals": tuple(jax.eval_shape(bwd_run, *in_avals)),
                    "run": bwd_run}
            if len(_abseval_cache) < _ABSEVAL_CACHE_MAX:
                _abseval_cache[key] = meta
        if lazy_enabled():
            return record_node(meta["run"],
                               list(res_values) + flat_cts,
                               list(meta["avals"]), key, label="bwd")
        vals = [concrete(v) for v in res_values] + \
            [concrete(c) for c in flat_cts]
        return meta["run"](*vals)

    vjp_fn._lazy_ok = True  # may receive LazyValue cotangents
    return vjp_fn
