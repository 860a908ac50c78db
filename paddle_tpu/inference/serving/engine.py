"""GenerationEngine: multi-request LLM serving over the paged KV cache.

Drives ``models/gpt.py`` as a continuous-batching server around ONE
unified ragged step program:

  * a single ``jit.to_static`` **step** over a fixed
    ``[1, token_budget]`` flat token buffer packs at most one prefill
    *chunk* plus every decode row into the same executable
    (ops/pallas_ragged.py) — the PR-5 pow2 prefill-bucket compile
    family is retired, so a mixed workload compiles ~1–2 programs
    total instead of ``len(buckets) + 1``.  The ragged cache view's
    driving arrays (slot mapping, block tables, context lengths,
    segment descriptors, sampling indices) are read-only state Tensors
    whose values the engine swaps before every call; the pool tensors
    are mutated state (donated, updated in place);
  * **prefix caching**: admission consults the COW prefix index
    (kv_cache.py) — a request sharing an already-cached prompt prefix
    starts prefill at the first uncached block, and each landed chunk
    commits its full blocks back to the index;
  * sampling happens **in-graph** (``ragged_sample_next``): greedy
    argmax, temperature, per-request top-k and top-p, with each draw
    keyed by ``fold_in(PRNGKey(request.seed), absolute_position)`` —
    deterministic under any schedule, chunking, batch packing, or
    preemption.  A step with no sampling row takes the argmax alone:
    the filter and the draw sit under one ``lax.cond`` on the step's
    own ``do_sample``/``temperature`` (``stats()['sampler_filter_steps']``
    counts the steps that ran them);
  * the step loop never blocks the host: decode input ids are the
    previous step's device-side output array (an eager device scatter,
    no host read), and results drain lazily ``pipeline_depth - 1``
    steps behind dispatch through the PR-4 in-flight window;
  * **speculative decoding** (``speculative=`` / PADDLE_TPU_SPEC_K,
    serving/speculative.py): a proposer drafts up to k tokens per
    decode row and the SAME compiled step verifies all k+1 positions at
    once — each spec row is a (k+1)-token prefill-like segment, the
    sampler reads k+1 columns (``last_index``/``sample_pos`` go
    ``[S, C]``), acceptance is deterministic token matching, and
    rejection is one paged-cache ``truncate()``.  Output is
    bit-identical to the non-speculative engine.  Spec steps drain
    host-synchronously (the accept decision gates the next feed), so
    ``speculative=None`` keeps the device-fed pipelined loop untouched;
  * **SLO multi-tenant serving** (``slo=`` + serving/slo.py): an
    :class:`~.slo.SLOPolicy` plugs into all three scheduler policy
    hooks (admission, victim, token budget) and the engine feeds it
    per-token/TTFT/finish callbacks for quota charging and
    ``serving.slo_violations`` accounting;
  * **streaming** (``generate(stream=True)`` + serving/streaming.py):
    tokens are pushed into bounded per-request :class:`TokenStream`
    queues as they are committed and yielded as
    :class:`~.streaming.StreamEvent` tuples;
  * observability: ``prefill:chunk`` / ``decode`` timeline lanes, and
    ``serving.tokens_per_sec`` / ``serving.ttft_ms`` /
    ``serving.prefix_hit_rate`` / ``serving.kv_blocks_shared`` /
    ``serving.queue_depth`` metrics, plus per-tenant token instants
    feeding ``phase_breakdown()["tenants"]``.

See README.md §"Serving" for usage and knobs.
"""
from __future__ import annotations

import collections
import contextlib
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ... import observability as obs
from ...core.dispatch import dispatch
from ...core.tensor import Tensor
from ...core.autograd import no_grad
from ...core.pipeline import pipeline_depth
from ...distributed.fault_tolerance.plan import fault_point
from ...incubate.nn.functional import _nucleus_mask
from ...ops.pallas_ragged import ragged_q_block
from .errors import RequestRejected, ServingStepTimeout
from .kv_cache import PagedKVCache
from .attention import RaggedCacheView
from .scheduler import (ContinuousBatchingScheduler, Request,
                        max_batch_size, prefill_chunk_size)
from .speculative import SpeculativeConfig
from .streaming import TokenStream

__all__ = ["GenerationEngine", "ragged_sample_next",
           "ENV_STEP_DEADLINE_MS",
           "ENV_SHED_DEPTH", "ENV_KV_DTYPE", "ENV_WEIGHT_DTYPE"]

#: per-step wall-clock deadline in ms (watchdog; unset/empty disables)
ENV_STEP_DEADLINE_MS = "PADDLE_TPU_SERVE_STEP_DEADLINE_MS"
#: admission load-shedding bound on queue depth (unset/0 disables)
ENV_SHED_DEPTH = "PADDLE_TPU_SERVE_SHED_DEPTH"
#: KV pool element dtype override ("int8" quantizes the paged cache
#: with per-slot dequant scales; unset = the model's param dtype)
ENV_KV_DTYPE = "PADDLE_TPU_KV_DTYPE"
#: weight dtype override ("int8" converts every Linear to weight-only
#: int8 with the dequant-fused matmul epilogue; unset = float weights)
ENV_WEIGHT_DTYPE = "PADDLE_TPU_WEIGHT_DTYPE"


# ---------------------------------------------------------------------
# in-graph sampling
# ---------------------------------------------------------------------
def _filter_and_draw(z, seeds, positions, do_sample, top_k, top_p,
                     temperature):
    """z [B, V] f32 -> next token [B] int64 (see _ragged_sample_impl)."""
    V = z.shape[-1]
    greedy = jnp.argmax(z, axis=-1)

    temp = temperature.astype(jnp.float32)
    z_t = z / jnp.where(temp > 0, temp, 1.0)[:, None]
    p = jax.nn.softmax(z_t, axis=-1)
    # per-row k: static jax.lax.top_k can't vary by row, so threshold
    # against the kth largest probability (k <= 0 keeps everything)
    k = jnp.clip(top_k.astype(jnp.int32), 0, V)
    p_desc = jnp.flip(jnp.sort(p, axis=-1), axis=-1)
    kth = jnp.take_along_axis(p_desc, jnp.maximum(k - 1, 0)[:, None],
                              axis=-1)
    p = jnp.where((k > 0)[:, None] & (p < kth), 0.0, p)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    p = jnp.where(_nucleus_mask(p, top_p.astype(jnp.float32)), p, 0.0)
    logp = jnp.log(jnp.maximum(p, 1e-30))

    def draw(seed, position, row_logp):
        key = jax.random.fold_in(
            jax.random.PRNGKey(seed.astype(jnp.uint32)),
            position.astype(jnp.uint32))
        return jax.random.categorical(key, row_logp)

    sampled = jax.vmap(draw)(seeds, positions, logp)
    use_sample = do_sample & (temp > 0)
    return jnp.where(use_sample, sampled, greedy).astype(jnp.int64)


def _sample_rows(z, seeds, positions, do_sample, top_k, top_p,
                 temperature):
    """`_filter_and_draw` only when a row of the step samples: with
    every row greedy (or at temperature 0) the softmax, the sorts, the
    nucleus scatter and the draw cannot change a token, and the step
    takes the argmax of the same float32 rows instead."""
    return jax.lax.cond(
        jnp.any(do_sample & (temperature.astype(jnp.float32) > 0)),
        lambda: _filter_and_draw(z, seeds, positions, do_sample, top_k,
                                 top_p, temperature),
        lambda: jnp.argmax(z, axis=-1).astype(jnp.int64))


def _ragged_sample_impl(logits, last_index, seeds, positions, do_sample,
                        top_k, top_p, temperature):
    """logits [1, T, V] (flat ragged step) -> next tokens, int64.

    With 1-D ``last_index`` [S]: sequence s reads the flat row
    ``last_index[s]`` — its last valid query this step — and the result
    is [S].  With 2-D ``last_index`` [S, C] (speculative verify):
    column j reads the logits following draft prefix d_1..d_j, and the
    per-row controls (seed, filters) are broadcast across the C
    columns, so every column draws with the key the sequential step
    would have used at that absolute position — the result is [S, C].
    Rows/columns that scheduled no sampling token this step
    (mid-prefill, idle, width < C) read a clamped/stale index and
    produce garbage the engine never drains.

    Greedy rows take the argmax; sampling rows apply temperature ->
    top-k -> top-p (the dense baseline's filter order) and draw with a
    key folded from (seed, absolute position), so the result does not
    depend on how the scheduler packed or when it ran this row.  The
    gather of the read rows is all a step without a sampling row pays
    beside the argmax (`_sample_rows`)."""
    li = last_index.astype(jnp.int32)
    if li.ndim == 1:
        z = logits[0, li].astype(jnp.float32)
        return _sample_rows(z, seeds, positions, do_sample, top_k,
                            top_p, temperature)
    S, C = li.shape
    z = logits[0, li.reshape(-1)].astype(jnp.float32)
    rep = lambda a: jnp.repeat(a, C, axis=0)  # noqa: E731
    out = _sample_rows(z, rep(seeds), positions.reshape(-1),
                       rep(do_sample), rep(top_k), rep(top_p),
                       rep(temperature))
    return out.reshape(S, C)


def ragged_sample_next(logits, last_index, seeds, positions, do_sample,
                       top_k, top_p, temperature):
    """Next-token selection over the flat ragged step's logits."""
    with obs.block("sampler"):
        return dispatch("ragged_sample_next", _ragged_sample_impl,
                        (logits, last_index, seeds, positions, do_sample,
                         top_k, top_p, temperature), {},
                        differentiable=False)


# ---------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------
class GenerationEngine:
    """Multi-request generation over one causal-LM model.

    ``add_request()`` enqueues, ``step()`` advances the whole batch one
    unified ragged step, ``generate()`` is the run-to-completion
    convenience.  Results are full token sequences (prompt + generated,
    truncated at EOS).
    """

    def __init__(self, model, config=None, max_batch=None,
                 block_size=None, num_blocks=None, max_model_len=None,
                 prefill_chunk=None, hbm_fraction=0.3,
                 prefix_cache=None, speculative=None, slo=None,
                 step_deadline_ms=None, shed_depth=None, clock=None,
                 kv_cache_dtype=None, weight_dtype=None,
                 role="colocated", kv_tiering=None, kv_host_budget=None,
                 resident_name=None):
        import paddle_tpu as paddle
        cfg = config or getattr(model, "config", None) \
            or model.gpt.config
        self.model = model
        model.eval()
        # disaggregated topology (disagg.py): a "prefill" engine runs
        # chunked prefill only and hands prompt-complete requests off;
        # a "decode" engine adopts them via inject_request.  The
        # default "colocated" interleaves both in one step as before.
        if role not in ("colocated", "prefill", "decode"):
            raise ValueError(f"unknown engine role {role!r}")
        self.role = role
        if weight_dtype is None:
            weight_dtype = os.environ.get(ENV_WEIGHT_DTYPE) or None
        if weight_dtype is not None and str(weight_dtype) == "int8":
            from ...quantization import convert_to_int8
            convert_to_int8(model)  # no-op on already-converted layers
        self.max_model_len = int(min(
            max_model_len or cfg.max_position_embeddings,
            cfg.max_position_embeddings))
        param = next(iter(model.parameters()))
        if kv_cache_dtype is None:
            kv_cache_dtype = os.environ.get(ENV_KV_DTYPE) or param.dtype
        self.max_batch = int(max_batch or max_batch_size())
        chunk = min(int(prefill_chunk or prefill_chunk_size()),
                    self.max_model_len)
        self.prefill_chunk = max(1, chunk)
        # geometry from the model's own per-layer cache spec: which
        # layers page K/V (with how many KV heads, in which group: a
        # layer with a window is in a group with tables of its own,
        # sized for a row's window and one chunk), which keep a
        # recurrent state or pooled keys per live request (a state
        # slot a row of the batch; the cache takes none without them)
        self.cache = PagedKVCache(
            layer_specs=model.cache_spec(), dtype=kv_cache_dtype,
            block_size=block_size, num_blocks=num_blocks,
            max_model_len=self.max_model_len, hbm_fraction=hbm_fraction,
            prefix_cache=prefix_cache, tiering=kv_tiering,
            host_budget=kv_host_budget, resident_name=resident_name,
            state_slots=self.max_batch, window_span=self.prefill_chunk)

        # unified step geometry: one prefill chunk (padded to whole
        # q-blocks) + one q-block per decode row, ALL in a single
        # fixed-shape program — token_budget never changes, so the
        # engine compiles once.  block_q follows the COMPUTE dtype (the
        # q buffer is never int8), so an int8 KV pool keeps the same
        # step geometry as its bf16 baseline.
        from ...core.dtypes import to_jax_dtype
        self.block_q = ragged_q_block(to_jax_dtype(param.dtype))
        chunk_pad = -(-self.prefill_chunk // self.block_q) * self.block_q
        self.token_budget = (chunk_pad
                             + (self.max_batch - 1) * self.block_q)
        self.num_q_blocks = self.token_budget // self.block_q

        # SLO policy (slo.py): one object drives all three scheduler
        # policy hooks plus the engine's accounting callbacks
        self.slo = slo
        self.scheduler = ContinuousBatchingScheduler(
            self.cache, self.max_batch, self.prefill_chunk,
            victim_policy=slo, admission_policy=slo, budget_policy=slo,
            prefill_only=(self.role == "prefill"))

        # speculative decoding (speculative.py): verify segments are
        # k+1 tokens wide and must fit one q-block
        self.spec = SpeculativeConfig.resolve(speculative)
        self.proposer = None
        self.spec_cols = 1
        if self.spec is not None and (self.cache.state_slots
                                      or self.cache.window_groups):
            raise ValueError(
                "speculative decoding rolls rejected drafts back with "
                "truncate(); a recurrent state, pooled keys or blocks a "
                "window gave back cannot be rolled back, so this model "
                "decodes without it")
        if self.spec is not None:
            self.spec.k = max(1, min(self.spec.k, self.block_q - 1))
            self.spec_cols = self.spec.k + 1
            self.proposer = self.spec.build_proposer(self)

        self._view = RaggedCacheView(self.cache, self.block_q,
                                     chunk_rows=chunk_pad)
        # cumulative, under their stats() names: what the steps carried
        # (decode rows, prompt tokens and the chunks they came in), the
        # first chunks run for models with per-request state, what
        # the layer caches count as they stage (`stage_state`), and the
        # steps packed with how many of them held a sampling row (the
        # sampler's filter ran: `_sample_rows`)
        self._counters = dict.fromkeys((
            "decode_rows_carried", "prompt_tokens_carried",
            "prefill_chunks", "state_resets", "sampler_steps",
            "sampler_filter_steps"), 0)
        # how many layers read which group's blocks (the grouped path's
        # counters are host arithmetic from each row's position)
        self._window_layers = collections.Counter(
            s.get("window") for s in self.cache.layer_specs
            if s["kind"] == "paged_kv") if self._view.grouped else {}
        # the latent layers and their query heads (the latent kernel's
        # work counters are host arithmetic from each row's position)
        latent = [int(s["query_heads"]) for s in self.cache.layer_specs
                  if s["kind"] == "latent"]
        self._latent = (len(latent), max(latent)) if latent else None
        self._step_reports = None
        self._step_fn = paddle.jit.to_static(self._ragged_step)
        # a device trace knows the step as jit_engine_step, and
        # observability.program_blocks() as "engine:step"
        self._step_fn.program_label = "engine:step"
        self._packed_context = 0

        # fault-tolerance knobs: a per-step wall-clock deadline (the
        # decode watchdog) and an admission queue-depth bound (load
        # shedding).  The clock is injectable so watchdog tests are
        # deterministic (same pattern as slo.py).
        self.clock = clock or time.perf_counter
        if step_deadline_ms is None:
            v = os.environ.get(ENV_STEP_DEADLINE_MS, "")
            step_deadline_ms = float(v) if v else None
        self.step_deadline_ms = (float(step_deadline_ms)
                                 if step_deadline_ms else None)
        if shed_depth is None:
            v = os.environ.get(ENV_SHED_DEPTH, "")
            shed_depth = int(v) if v else 0
        self.shed_depth = int(shed_depth or 0)

        # multi-LoRA tenancy (lora.py): enable_lora() builds the paged
        # adapter store and the per-q-block segment descriptor BEFORE
        # the first trace; requests then carry an adapter id
        self._lora = None
        self._lora_held = {}      # req.id -> adapter pinned for it

        self._rows = [None] * self.max_batch
        self._last_tokens = jnp.zeros((self.max_batch,), jnp.int64)
        self._pending = []   # [(rows_reqs, device_tokens, reports)]
        self._results = {}        # req.id -> Request
        self._streams = {}        # req.id -> TokenStream
        self._req_counter = 0
        self._step_idx = 0
        self._step_finished = []
        self._tokens_generated = 0
        self._tokens_drafted = 0
        self._tokens_accepted = 0
        self._step_tenant_tokens = {}
        self._step_timeouts = 0
        self._step_aborts = 0
        self._shed_requests = 0
        self._alloc_fails = 0

    # -- the ONE traced step function -----------------------------------
    def _ragged_step(self, ids, seeds, do_sample, top_k, top_p,
                     temperature):
        view = self._view
        with no_grad():
            logits = self.model(ids, cache=view, use_cache=False)
            tok = ragged_sample_next(
                logits, view.last_index, seeds, view.sample_pos,
                do_sample, top_k, top_p, temperature)
            # what the layers counted in the step (an expert layer's
            # plan) leaves the program beside the tokens
            reports = view.take_reports()
            return (tok, reports) if reports else tok

    # -- multi-LoRA tenancy ---------------------------------------------
    def enable_lora(self, rank=8, alpha=None, targets=None,
                    num_slots=None, budget=None):
        """Build the paged adapter store over this engine's model and
        stage the all-null segment descriptor.  MUST run before the
        first step (the ONE compiled program reads the descriptor and
        the store's device stacks as staged state — enabling later
        would mean a second program).  Without an explicit size, the
        ``PADDLE_TPU_LORA_STORE_BUDGET`` env sizes the store, falling
        back to ``max_batch`` slots — enough that every running row
        can pin a distinct adapter, so admission never starves.
        Returns the store."""
        from .lora import (LoRAAdapterStore, SegmentAdapterState,
                           attach_lora_sites, lora_store_budget)
        if self._lora is not None:
            return self._lora.store
        if len(self._step_fn._cache):
            raise RuntimeError(
                "enable_lora() must run before the first step: the "
                "compiled step program is already traced without the "
                "adapter epilogue")
        sites = attach_lora_sites(self.model, targets=targets)
        param = next(iter(self.model.parameters()))
        if num_slots is None and budget is None \
                and lora_store_budget() is None:
            num_slots = self.max_batch
        store = LoRAAdapterStore(
            sites, rank, dtype=param.dtype, alpha=alpha,
            num_slots=num_slots, budget=budget)
        self._lora = SegmentAdapterState(store, self.block_q)
        self._lora.stage(np.full(self.num_q_blocks, store.null_slot,
                                 np.int32))
        self._view.set_lora(self._lora)
        return store

    def register_adapter(self, name, weights, alpha=None, rank=None):
        """Land one adapter in the store's host tier (see
        ``LoRAAdapterStore.register_adapter``); requires
        ``enable_lora()`` first."""
        if self._lora is None:
            raise RuntimeError("enable_lora() first")
        return self._lora.store.register_adapter(name, weights,
                                                 alpha=alpha, rank=rank)

    def _lora_acquire(self, req):
        """Pin the request's adapter into a device slot (idempotent —
        a requeued request re-admits without double-counting)."""
        if self._lora is None or req.adapter is None:
            return
        if req.id in self._lora_held:
            return
        self._lora.store.acquire(req.adapter)
        self._lora_held[req.id] = req.adapter

    def _lora_release(self, req):
        """Drop the request's pin; the slot parks LRU-evictable."""
        if self._lora is None:
            return
        name = self._lora_held.pop(req.id, None)
        if name is not None:
            self._lora.store.release(name)

    # -- public API -----------------------------------------------------
    def add_request(self, prompt, max_new_tokens=16, do_sample=False,
                    top_k=0, top_p=1.0, temperature=1.0, seed=0,
                    eos_token_id=None, request_id=None, tenant=None,
                    adapter=None):
        """Enqueue one prompt; returns the request id.  ``adapter``
        selects a registered LoRA adapter (None = base model); a
        tenant-tagged request with no explicit adapter inherits its
        ``TenantSpec.adapter``."""
        if adapter is None and tenant is not None and self.slo is not None:
            spec = self.slo.tenants.get(tenant)
            if spec is not None:
                adapter = spec.adapter
        if adapter is not None:
            if self._lora is None:
                raise ValueError(
                    f"adapter={adapter!r} requires enable_lora()")
            if not self._lora.store.has_adapter(adapter):
                raise KeyError(f"adapter {adapter!r} is not registered")
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) >= self.max_model_len:
            raise ValueError(
                f"prompt length {len(prompt)} >= max_model_len "
                f"{self.max_model_len}")
        max_new_tokens = min(int(max_new_tokens),
                             self.max_model_len - len(prompt))
        depth = self.scheduler.queue_depth
        if self.shed_depth and depth >= self.shed_depth:
            # backpressure: overload degrades to a fast structured
            # rejection (the 429 path) instead of a TTFT collapse
            self._shed_requests += 1
            obs.get_registry().counter("serving.shed_requests").inc()
            obs.instant("serving.shed", cat="fault", queue_depth=depth,
                        shed_depth=self.shed_depth)
            raise RequestRejected(
                "overloaded", queue_depth=depth,
                shed_depth=self.shed_depth,
                request_id=request_id or f"req{self._req_counter}")
        if request_id is None:
            request_id = f"req{self._req_counter}"
        self._req_counter += 1
        req = Request(request_id, prompt, max_new_tokens=max_new_tokens,
                      do_sample=do_sample, top_k=top_k, top_p=top_p,
                      temperature=temperature, seed=seed,
                      eos_token_id=eos_token_id, tenant=tenant,
                      adapter=adapter)
        self.scheduler.submit(req)
        obs.get_registry().gauge("serving.queue_depth").set(
            self.scheduler.queue_depth)
        return request_id

    def has_unfinished(self):
        return self.scheduler.has_work() or bool(self._pending)

    def step(self):
        """One unified ragged step (admissions + at most one prefill
        chunk + every decode row) plus a lazy drain.  Returns the
        requests that finished this step."""
        self._step_idx += 1
        with obs.span("engine:step", boundary=True, step=self._step_idx):
            return self._step()

    def _step(self):
        self._step_finished = []
        self._step_tenant_tokens = {}
        with obs.span("engine:schedule", boundary=True):
            action, payload = self._schedule()
        if action == "step":
            if self.proposer is not None:
                self._run_spec_step(payload)
            else:
                self._run_step(payload)
        elif self._pending:
            self._drain(0)       # nothing to schedule: retire in flight
        # a prefill engine drains eagerly: its product is a handoff,
        # and extract_request needs no token still in flight
        lag = 0 if self.role == "prefill" \
            else max(0, pipeline_depth() - 1)
        self._drain(lag)
        with obs.span("engine:collect", boundary=True):
            self._collect_finished()
            reg = obs.get_registry()
            reg.gauge("serving.queue_depth").set(
                self.scheduler.queue_depth)
            for t, n in self._step_tenant_tokens.items():
                reg.counter(f"serving.tenant.{t}.tokens").inc(n)
                obs.instant("serving.tenant.tokens", cat="decode",
                            step=self._step_idx, tenant=t, n=n)
        return list(self._step_finished)

    def _schedule(self):
        """Ask the scheduler for this step's action, admitting queued
        requests on the way."""
        allow_admission = True
        while True:
            action, payload = self.scheduler.next_action(allow_admission)
            if action != "admit":
                return action, payload
            try:
                self._admit(payload)
            except Exception as e:
                # allocation failed (e.g. injected serve.alloc_fail):
                # allocate() raises before any pool mutation and
                # begin_prefill before any queue mutation, so the
                # request simply stays at the queue head and retries
                # NEXT step — admission closes for the rest of THIS
                # step so one fault cannot retry-loop it.
                allow_admission = False
                self._alloc_fails += 1
                obs.get_registry().counter(
                    "serving.alloc_fails").inc()
                obs.instant("serving.alloc_fail", cat="fault",
                            request=payload.id,
                            error=f"{type(e).__name__}: {e}"[:200])

    # -- disaggregated handoff (disagg.py) -------------------------------
    def handoff_ready(self):
        """Requests whose prompt K/V is complete and first token is
        sampled — a prefill engine's finished product, waiting to move
        to a decode engine."""
        return [r for r in self.scheduler.running
                if not r.done and not r.prefilling and r.generated]

    def extract_request(self, req):
        """Pull a prompt-complete request out of this engine together
        with its paged KV state as a host payload.  The request leaves
        running and its blocks are freed WITH their tokens, so they
        park prefix-indexed: the next request sharing this prompt
        still prefills warm here.  Returns (payload, length,
        stream)."""
        if req not in self.scheduler.running:
            raise KeyError(f"{req.id!r} is not running here")
        if req.prefilling or not req.generated:
            raise ValueError(f"{req.id!r} is not handoff-ready")
        if self._pending:
            self._drain(0)        # no token may still be in flight
        length = self.cache.length(req.id)
        payload = self.cache.export_sequence(req.id)
        tokens = (list(req.prompt) + list(req.generated))[:length]
        if req.row is not None:
            self._rows[req.row] = None
            req.row = None
        self.scheduler.running.remove(req)
        self.cache.free(req.id, tokens=tokens)
        self._lora_release(req)
        if self.proposer is not None:
            self.proposer.drop(req.id)
        stream = self._streams.pop(req.id, None)
        obs.instant("serving.handoff_out", cat="prefill",
                    request=req.id, blocks=payload.num_blocks)
        return payload, length, stream

    def inject_request(self, req, length, payload, stream=None):
        """Seat a request whose prompt K/V was prefilled on ANOTHER
        engine (disaggregated decode).  Imports the blocks through the
        local prefix cache (already-cached blocks are skipped, not
        copied), seats a batch row, and primes the device-side token
        feed with the request's last sampled token — the next decode
        step proceeds exactly as if the prefill had run here.  Returns
        False (nothing mutated) when no row or blocks are available."""
        if req.id in self.cache:
            raise KeyError(f"sequence {req.id!r} already allocated")
        if None not in self._rows:
            return False
        if req.adapter is not None:
            if self._lora is None \
                    or not self._lora.store.has_adapter(req.adapter):
                raise KeyError(
                    f"adapter {req.adapter!r} is not registered here")
        tokens = (list(req.prompt) + list(req.generated))[:length]
        if not self.cache.import_sequence(req.id, tokens, length,
                                          payload,
                                          adapter=req.adapter):
            return False
        self._lora_acquire(req)
        row = self._rows.index(None)
        self._rows[row] = req
        req.row = row
        req.num_computed = len(req.prompt)
        req.cached_prefix = self.cache.cached_prefix_len(req.id)
        self.scheduler.adopt(req)
        # the colocated engine's own prefill would have left the first
        # sampled token in this row's slot of _last_tokens; recreate it
        self._last_tokens = self._last_tokens.at[row].set(
            int(req.generated[-1]))
        if stream is not None:
            self._streams[req.id] = stream
        obs.instant("serving.handoff_in", cat="decode",
                    request=req.id, blocks=payload.num_blocks)
        return True

    def generate(self, prompts, stream=False, **kwargs):
        """Run a batch of prompts to completion.

        ``stream=False``: returns one full token list
        (prompt + generated) per prompt, in order.
        ``stream=True``: returns a generator of
        :class:`~.streaming.StreamEvent` tuples, yielding each token as
        it is committed (decode drain or speculative acceptance)
        instead of waiting for completions."""
        if stream:
            return self._generate_stream(prompts, **kwargs)
        ids = [self.add_request(p, **kwargs) for p in prompts]
        t0 = time.perf_counter()
        n0 = self._tokens_generated
        while self.has_unfinished():
            self.step()
        elapsed = time.perf_counter() - t0
        if elapsed > 0:
            obs.get_registry().gauge("serving.tokens_per_sec").set(
                (self._tokens_generated - n0) / elapsed)
        return [self.result(i) for i in ids]

    def open_stream(self, request_id):
        """Bounded live token queue for an enqueued request; the engine
        pushes committed tokens into it during step()."""
        st = self._streams.get(request_id)
        if st is None:
            st = self._streams[request_id] = TokenStream(request_id)
        return st

    def _generate_stream(self, prompts, **kwargs):
        ids = [self.add_request(p, **kwargs) for p in prompts]
        streams = [self.open_stream(i) for i in ids]
        try:
            while True:
                if self.has_unfinished():
                    self.step()
                for st in streams:
                    for ev in st.drain():
                        yield ev
                if all(st.done for st in streams):
                    return
        finally:
            for i in ids:
                self._streams.pop(i, None)

    def result(self, request_id):
        """Full token sequence of a finished request."""
        req = self._results[request_id]
        return list(req.prompt) + list(req.generated)

    def stats(self):
        s = self.cache.stats()
        compiles = len(self._step_fn._cache)
        if self.proposer is not None:
            compiles += self.proposer.step_compiles
        s.update(role=self.role,
                 queue_depth=self.scheduler.queue_depth,
                 running=len(self.scheduler.running),
                 tokens_generated=self._tokens_generated,
                 tokens_drafted=self._tokens_drafted,
                 tokens_accepted=self._tokens_accepted,
                 spec_accept_rate=(self._tokens_accepted
                                   / self._tokens_drafted
                                   if self._tokens_drafted else 0.0),
                 token_budget=self.token_budget,
                 step_compiles=compiles,
                 **self._counters,
                 step_timeouts=self._step_timeouts,
                 step_aborts=self._step_aborts,
                 shed_requests=self._shed_requests,
                 alloc_fails=self._alloc_fails)
        if self._lora is not None:
            ls = self._lora.store.stats()
            s.update(lora=ls, adapter_hit_rate=ls["hit_rate"])
        return s

    def close(self):
        if self.proposer is not None:
            self.proposer.close()
        if self._lora is not None:
            self._lora.store.close()
        self.cache.close()

    # -- admission ------------------------------------------------------
    def _admit(self, req):
        """Allocate the prompt (prefix-aware) and seat the request."""
        # pin the adapter FIRST: an AdapterStoreFull leaves the
        # scheduler and the KV pool untouched
        self._lora_acquire(req)
        self.scheduler.begin_prefill(req)
        row = self._rows.index(None)
        self._rows[row] = req
        req.row = row
        if req.cached_prefix:
            obs.instant("serving.prefix_hit", cat="prefill",
                        request=req.id, cached=req.cached_prefix,
                        prompt=len(req.prompt))
        obs.get_registry().gauge("serving.prefix_hit_rate").set(
            self.cache.prefix_hit_rate)

    # -- the unified step -----------------------------------------------
    def _run_step(self, plan):
        appended = {}            # req.id -> length before this round
        while True:
            chunk, decodes = plan
            if self._reserve_slots(decodes, appended):
                break
            # preemption (or a finish) changed the schedule: slots
            # reserved this round were never dispatched — re-ask; if
            # the next action is no longer a step, roll back or the
            # surviving rows' context advances past their real tokens
            action, payload = self.scheduler.next_action()
            if action != "step":
                self._rollback_slots(appended)
                return
            plan = payload
        self._dispatch_step(chunk, decodes, appended)

    def _rollback_slots(self, appended):
        for rid, before in appended.items():
            if rid in self.cache:        # freed rows need no rollback
                self.cache.truncate(rid, before)

    def _reserve_slots(self, active, appended, widths=None):
        """Extend every decode sequence by its step width (1 slot, or
        1 + drafts under speculation); on pool exhaustion retire
        in-flight work, then preempt the policy's victim to the waiting
        queue.  Returns False when the active set changed."""
        for req in active:
            if req.id in appended:
                continue
            w = 1 if widths is None else widths.get(req.id, 1)
            before = self.cache.length(req.id)
            if self.cache.append(req.id, w):
                appended[req.id] = before
                continue
            self._drain(0)
            self._collect_finished()     # finished rows free blocks
            if req.done:
                return False             # freed itself: rebuild active
            if self.cache.append(req.id, w):
                appended[req.id] = before
                continue
            victim = self.scheduler.select_victim()
            if victim is None:
                raise RuntimeError(
                    "KV pool exhausted with nothing left to preempt")
            self._preempt(victim)
            appended.pop(victim.id, None)
            return False
        return True

    def _preempt(self, victim):
        """Requeue-by-recompute: all of the victim's tokens are already
        drained (the caller forced lag 0), so its prompt+generated
        resubmits at the head of the queue.  Its written blocks are
        prefix-indexed on free, so the resumed prefill keeps whatever
        the pool doesn't actually reclaim."""
        obs.instant("serving.preempt", cat="decode", request=victim.id,
                    generated=len(victim.generated))
        if victim.row is not None:
            self._rows[victim.row] = None
        self._lora_release(victim)
        if self.proposer is not None:
            self.proposer.drop(victim.id)
        self.scheduler.requeue(victim, victim.generated)

    def _abort_step(self, chunk, decodes, appended, kind, error):
        """Unwind a failed or hung step: retire everything already in
        flight from EARLIER steps, roll every reserved-but-undispatched
        slot back through the refcount-aware ``truncate()``, and requeue
        the affected requests with their committed progress.  Because
        sampling is keyed by (seed, absolute position), stepping again —
        here or on another replica — replays them bit-identically; the
        positions past the committed length were never prefix-indexed
        (``commit_prefix`` only hashes fully-covered blocks), so the
        garbage KV a half-run step may have written can never be shared.
        Returns the requeued request ids."""
        self._drain(0)                   # prior steps' tokens commit
        self._collect_finished()
        affected = []
        reqs = list(decodes)
        if chunk is not None and chunk.request not in reqs:
            reqs.append(chunk.request)
        for req in reqs:
            if req.done or req not in self.scheduler.running:
                continue
            if req.id in appended and req.id in self.cache:
                self.cache.truncate(req.id, appended[req.id])
            if req.row is not None:
                self._rows[req.row] = None
            self._lora_release(req)
            if self.proposer is not None:
                self.proposer.drop(req.id)
            self.scheduler.requeue(req, req.generated)
            affected.append(req.id)
        self._step_aborts += 1
        obs.get_registry().counter("serving.step_aborts").inc()
        obs.instant(f"serving.{kind}", cat="fault", step=self._step_idx,
                    requests=len(affected),
                    **({"error": f"{type(error).__name__}: {error}"
                        [:200]} if error is not None else {}))
        return affected

    def _checked_dispatch(self, ids_t, args, chunk, decodes, appended):
        """The ONE device dispatch, wrapped by the chaos sites and the
        decode watchdog.  A raising step (injected ``serve.step_fail``
        or a real error) aborts-and-requeues then re-raises; a step that
        outlives ``step_deadline_ms`` (injected ``serve.step_hang``
        stalls here) aborts-and-requeues then raises the structured
        :class:`ServingStepTimeout`."""
        t0 = self.clock()
        try:
            fault_point("serve.step_fail")
            tok = self._step_fn(ids_t, *args)
            self._step_reports = None
            if isinstance(tok, (tuple, list)):
                tok, self._step_reports = tok
            fault_point("serve.step_hang")
        except Exception as e:
            self._abort_step(chunk, decodes, appended, "step_fail", e)
            raise
        elapsed_ms = (self.clock() - t0) * 1e3
        if (self.step_deadline_ms is not None
                and elapsed_ms > self.step_deadline_ms):
            self._step_timeouts += 1
            obs.get_registry().counter("serving.step_timeouts").inc()
            affected = self._abort_step(chunk, decodes, appended,
                                        "step_timeout", None)
            raise ServingStepTimeout(self._step_idx, elapsed_ms,
                                     self.step_deadline_ms,
                                     requests=affected)
        return tok

    def _dispatch_step(self, chunk, decodes, appended):
        """Pack the chunk + decode rows into the flat ragged buffer and
        dispatch the ONE compiled step."""
        if self._view.grouped:
            with obs.span("engine:release", boundary=True):
                self._release_passed(chunk, decodes)
        with obs.span("engine:pack", boundary=True):
            ids_t, args, rows_reqs = self._pack_step(chunk, decodes)
        tok = self._spanned_dispatch(ids_t, args, chunk, decodes,
                                     appended)
        self._last_tokens = tok._value
        for _, req in rows_reqs:
            req.n_scheduled += 1
        reports = self._step_reports and {
            name: t._value for name, t in self._step_reports.items()}
        if rows_reqs or reports:
            self._pending.append((rows_reqs, tok._value, reports))
        if chunk is not None:
            req = chunk.request
            req.num_computed = chunk.start + chunk.length
            # landed blocks join the prefix index for future sharers
            self.cache.commit_prefix(
                req.id, req.prompt[:req.num_computed])

    def _release_passed(self, chunk, decodes):
        """Before the step is packed: every windowed group gives back
        the blocks that lie wholly behind the window of each row's first
        token of this step and holds the chunk's new ones; and the
        grouped layers' block counters, host arithmetic from each row's
        position: K/V blocks (all KV heads) the step's decode rows and
        the chunk's q-blocks read in the windowed and in the full
        layers, what the windowed layers would have read with no
        window, and the table slots their calls are handed
        (``kv_table_slots``: the width of the group's table a q-block,
        for a windowed layer's decode rows what `RaggedLayerCache` cuts
        it to): the blocks read over it is the share of a table that the
        kernel's walk has to visit.  With latent layers, their kernel's
        work: the latent blocks its walks fetch (a decode row's whole
        context, a chunk q-block's up to its last token, once for all
        heads) and the (query head row, visible key) pairs it computes
        on (``latent_blocks_read``, ``latent_pairs``)."""
        cache, bs = self.cache, self.cache.block_size
        spans = [(cache.length(r.id) - 1, 1, r.id) for r in decodes]
        reads = [(p, p) for p, _, _ in spans]    # (first, last) token
        if chunk is not None:
            req, start, n = chunk
            spans.append((start, n, req.id))
            bq = self._view.chunk_block_q
            reads += [(a, min(a + bq, start + n) - 1)
                      for a in range(start, start + n, bq)]
        released = sum(cache.write_window(rid, start, n)
                       for start, n, rid in spans)
        step = dict.fromkeys(("kv_blocks_read_window", "kv_blocks_read_full",
                              "kv_blocks_context", "kv_table_slots"), 0)
        step["window_blocks_released"] = released
        widths = {g.window: g.table_width for g in cache.window_groups}
        for window, layers in self._window_layers.items():
            width = widths.get(window, cache.table_width)
            dec_width = width if window is None else min(
                width, window // bs + 2)
            step["kv_table_slots"] += layers * (
                len(decodes) * dec_width
                + (len(reads) - len(decodes)) * width)
            for first, last in reads:
                whole = last // bs + 1
                if window is None:
                    step["kv_blocks_read_full"] += layers * whole
                else:
                    step["kv_blocks_context"] += layers * whole
                    step["kv_blocks_read_window"] += layers * (
                        whole - max(0, first - window + 1) // bs)
        if self._latent:
            layers, heads = self._latent
            ctxs = [p + 1 for p, _, _ in spans[:len(decodes)]]
            blocks = sum(-(-c // bs) for c in ctxs)
            pairs = sum(ctxs)
            if chunk is not None:
                _, start, n = chunk
                ct = self._view.latent_chunk_tokens
                ctx = -(-(start + n) // bs)
                blocks += sum(min(ctx, (a + ct - 1) // bs + 1)
                              for a in range(start, start + n, ct))
                pairs += n * start + n * (n + 1) // 2
            step["latent_blocks_read"] = layers * blocks
            step["latent_pairs"] = layers * heads * pairs
        self._count(step)

    def _count(self, step):
        """A step's counts into the cumulative counters (`stats()`), and
        into the registry while observability is on (``state.resets``,
        ``sparse.*``, ``moe.*``, ``window.*``, ``kv.*``)."""
        for name, n in step.items():
            self._counters[name] = self._counters.get(name, 0) + n
            if n and obs.enabled():
                obs.get_registry().counter(
                    name.replace("_", ".", 1)).inc(n)

    def _spanned_dispatch(self, ids_t, args, chunk, decodes, appended,
                          **decode_attrs):
        """`_checked_dispatch` under its spans: ``engine:dispatch`` on
        the profiler's clock, and the timeline's ``decode`` /
        ``prefill:chunk`` (what the step carried) inside it.  The
        boundary span says what the step carries: its decode rows, its
        chunk's tokens and first position (0 without one), and the
        context its rows attend to, summed (the packer's
        ``context_lens``)."""
        self._counters["decode_rows_carried"] += len(decodes)
        if chunk is not None:
            self._counters["prompt_tokens_carried"] += chunk.length
            self._counters["prefill_chunks"] += 1
        with contextlib.ExitStack() as stack:
            stack.enter_context(obs.span(
                "engine:dispatch", boundary=True, step=self._step_idx,
                decode_rows=len(decodes),
                chunk_tokens=chunk.length if chunk is not None else 0,
                chunk_start=chunk.start if chunk is not None else 0,
                context_tokens=self._packed_context))
            if decodes:
                stack.enter_context(obs.span(
                    "decode", cat="decode", step=self._step_idx,
                    batch=len(decodes), **decode_attrs))
            if chunk is not None:
                stack.enter_context(obs.span(
                    "prefill:chunk", cat="prefill", step=self._step_idx,
                    request=chunk.request.id, start=chunk.start,
                    tokens=chunk.length,
                    **({"tenant": chunk.request.tenant}
                       if chunk.request.tenant else {})))
            return self._checked_dispatch(ids_t, args, chunk, decodes,
                                          appended)

    def _pack_step(self, chunk, decodes):
        """The host's part of a step: the flat ragged buffer, the
        kernel's segment descriptors and the control tensors."""
        T, S, BQ = self.token_budget, self.max_batch, self.block_q
        W = self.cache.table_width
        NQB = self.num_q_blocks
        ids = np.zeros((1, T), np.int64)
        slots = np.zeros(T, np.int32)        # pad rows -> pad block 0
        positions = np.zeros((1, T), np.int64)
        seq_ids = np.full(NQB, S, np.int32)  # S = null segment
        q_starts = np.zeros(NQB, np.int32)
        q_valids = np.zeros(NQB, np.int32)
        tables = np.zeros((S, W), np.int32)
        ctx = np.zeros(S, np.int32)
        # a windowed group's own slots, tables and context bases
        groups = [(g, np.zeros(T, np.int32),
                   np.zeros((S, g.table_width), np.int32),
                   np.zeros(S, np.int32)) for g in self.cache.window_groups]

        def fill_groups(req, flat, start, n):
            for g, g_slots, g_tables, g_base in groups:
                g_slots[flat:flat + n] = g.slot_mapping(req.id, start, n)
                g_tables[req.row] = g.block_table(req.id)
                g_base[req.row] = g.context_base(req.id)

        last_index = np.zeros(S, np.int32)
        sample_pos = np.zeros(S, np.int64)
        lora_slots = None        # q-block -> adapter device slot
        if self._lora is not None:
            lora_slots = np.full(NQB, self._lora.store.null_slot,
                                 np.int32)

        flat = 0
        rows_reqs = []           # rows that sample a token this step
        decode_feed = []         # (flat_idx, row): device-token inputs
        for req in decodes:
            r = req.row
            length = self.cache.length(req.id)   # incl. this new slot
            seg = flat // BQ
            seq_ids[seg] = r
            q_starts[seg] = length - 1
            q_valids[seg] = 1
            if lora_slots is not None and req.adapter is not None:
                lora_slots[seg] = self._lora.store.slot_of(req.adapter)
            slots[flat] = self.cache.slot_mapping(
                req.id, length - 1, 1)[0]
            fill_groups(req, flat, length - 1, 1)
            positions[0, flat] = length - 1
            decode_feed.append((flat, r))
            tables[r] = self.cache.block_table(req.id)
            ctx[r] = length
            last_index[r] = flat
            sample_pos[r] = length
            rows_reqs.append((r, req))
            flat += BQ
        if chunk is not None:
            req, start, n = chunk
            r = req.row
            ids[0, flat:flat + n] = req.prompt[start:start + n]
            slots[flat:flat + n] = self.cache.slot_mapping(
                req.id, start, n)
            fill_groups(req, flat, start, n)
            positions[0, flat:flat + n] = np.arange(start, start + n)
            nseg = -(-n // BQ)
            for j in range(nseg):
                seq_ids[flat // BQ + j] = r
                q_starts[flat // BQ + j] = start + j * BQ
                q_valids[flat // BQ + j] = min(BQ, n - j * BQ)
            if lora_slots is not None and req.adapter is not None:
                lora_slots[flat // BQ:flat // BQ + nseg] = \
                    self._lora.store.slot_of(req.adapter)
            tables[r] = self.cache.block_table(req.id)
            ctx[r] = start + n
            if start + n == len(req.prompt):
                # prompt complete: sample the first new token
                last_index[r] = flat + n - 1
                sample_pos[r] = start + n
                rows_reqs.append((r, req))
            flat += nseg * BQ

        self._packed_context = int(ctx.sum())
        self._view.set_inputs(slots, tables, ctx, positions, seq_ids,
                              q_starts, q_valids, last_index,
                              sample_pos)
        for group in groups:
            self._view.set_group_inputs(*group)
        if self.cache.state_slots or self._view.grouped:
            self._stage_state(chunk, decodes)
        if lora_slots is not None:
            self._lora.stage(lora_slots)
        args = self._control_tensors(
            [self._rows[r] for r in range(S)], S)
        ids_dev = jnp.asarray(ids)
        if decode_feed:
            flat_idx = np.asarray([f for f, _ in decode_feed], np.int32)
            rows = np.asarray([r for _, r in decode_feed], np.int32)
            # previous step's device-side tokens feed this step's
            # inputs with no host read
            ids_dev = ids_dev.at[0, flat_idx].set(
                self._last_tokens[rows])
        ids_t = Tensor(ids_dev, _internal=True, stop_gradient=True)
        return ids_t, args, rows_reqs

    def _stage_state(self, chunk, decodes):
        """What the layers with per-request state read this step: each
        decode row's flat index, state slot and position, and the
        chunk's offset, rows, slot, first-chunk flag, batch row and
        start.  The layer caches stage what only they read from these,
        and hand back what they counted on the way."""
        T, S, BQ = self.token_budget, self.max_batch, self.block_q
        cache = self.cache
        dec_index = np.full(S, T, np.int32)      # T: dropped on scatter
        row_slots = np.zeros(S, np.int32)
        row_pos = np.full(S, -1, np.int32)       # -1: an idle row
        for i, req in enumerate(decodes):        # packed first, in order
            dec_index[req.row] = i * BQ
            row_slots[req.row] = cache.slot(req.id)
            row_pos[req.row] = cache.length(req.id) - 1
        # the chunk follows the decode rows in the flat buffer
        meta = np.asarray([len(decodes) * BQ if chunk is not None else 0,
                           0, 0, 0, S, 0], np.int32)
        if chunk is not None:
            req, start, n = chunk
            meta[1:] = (n, cache.slot(req.id), start == 0, req.row, start)
        step = self._view.stage_state(dec_index, row_slots, row_pos, meta)
        if cache.state_slots:
            step["state_resets"] = int(meta[3])
        self._count(step)

    # -- the speculative step -------------------------------------------
    def _run_spec_step(self, plan):
        """Spec variant of `_run_step`: propose -> reserve ``k_row + 1``
        slots per decode row -> ONE verify dispatch -> host-synchronous
        accept/rollback.  Proposals are deterministic (greedy draft /
        n-gram lookup over an unchanged history), so re-proposing after
        a preemption re-plan yields identical widths for surviving
        rows."""
        appended = {}            # req.id -> length before this round
        while True:
            chunk, decodes = plan
            drafts = self._propose(decodes)
            widths = {r.id: 1 + len(drafts.get(r.id, ()))
                      for r in decodes}
            if self._reserve_slots(decodes, appended, widths):
                break
            action, payload = self.scheduler.next_action()
            if action != "step":
                self._rollback_slots(appended)
                return
            plan = payload
        self._dispatch_spec_step(chunk, decodes, drafts, appended)

    def _propose(self, decodes):
        """Drafts for every decode row that still has room to speculate
        (``kmax >= 1`` after the remaining-token and max_model_len
        clamps; a row with no room verifies as a plain width-1 step)."""
        items = []
        for req in decodes:
            history = list(req.prompt) + list(req.generated)
            # the row's verify segment starts where its last committed
            # token will scatter (cache-length invariant; do NOT read
            # cache.length here — a re-plan retry may already have
            # appended this row's slots)
            base = len(history) - 1
            kmax = min(self.spec.k,
                       req.max_new_tokens - len(req.generated) - 1,
                       self.max_model_len - base - 1)
            if kmax >= 1:
                items.append((req, history, kmax))
        if not items:
            return {}
        return self.proposer.propose_batch(items)

    def _dispatch_spec_step(self, chunk, decodes, drafts, appended):
        """Pack the chunk + per-row verify segments (the row's last
        known token plus its drafts, one q-block each) into the flat
        buffer, dispatch the ONE compiled step, then read the ``[S, C]``
        samples back and accept the longest draft prefix that matches
        the target's own tokens.  Rejected positions roll back with one
        refcount-aware ``truncate()`` — the preemption-rollback path."""
        with obs.span("engine:pack", boundary=True):
            ids_t, args, spec_rows, chunk_row = self._pack_spec_step(
                chunk, decodes, drafts, appended)
        tok = self._spanned_dispatch(ids_t, args, chunk, decodes,
                                     appended, spec=True)
        # the accept decision gates the next step's feed, so spec steps
        # drain host-synchronously (no _pending window)
        with obs.span("engine:drain", boundary=True, lag=0):
            host = np.asarray(tok._value)

        for req, base, d in spec_rows:
            if req.done:
                continue
            row_tok = host[req.row]
            # column j is the target's token following draft prefix
            # d[:j]; accept while the draft agrees with the target
            a = 0
            while a < len(d) and int(row_tok[a]) == d[a]:
                a += 1
            self._tokens_drafted += len(d)
            self._tokens_accepted += a
            committed = 0
            for j in range(a + 1):       # accepted prefix + bonus token
                self._commit_token(req, int(row_tok[j]))
                committed += 1
                if req.done:
                    break
            # positions past the last committed token hold rejected
            # drafts: roll the paged cache back to the verified length
            self.cache.truncate(req.id, base + committed)
            req.n_scheduled = len(req.generated)
            self.proposer.commit(req.id, base + 1 + a)
        if chunk_row is not None:
            r, req = chunk_row
            if not req.done:
                self._commit_token(req, int(host[r, 0]))
                req.n_scheduled = len(req.generated)
        if chunk is not None:
            req = chunk.request
            req.num_computed = chunk.start + chunk.length
            self.cache.commit_prefix(
                req.id, req.prompt[:req.num_computed])

    def _pack_spec_step(self, chunk, decodes, drafts, appended):
        T, S, BQ = self.token_budget, self.max_batch, self.block_q
        C = self.spec_cols
        W = self.cache.table_width
        NQB = self.num_q_blocks
        ids = np.zeros((1, T), np.int64)
        slots = np.zeros(T, np.int32)        # pad rows -> pad block 0
        positions = np.zeros((1, T), np.int64)
        seq_ids = np.full(NQB, S, np.int32)  # S = null segment
        q_starts = np.zeros(NQB, np.int32)
        q_valids = np.zeros(NQB, np.int32)
        tables = np.zeros((S, W), np.int32)
        ctx = np.zeros(S, np.int32)
        last_index = np.zeros((S, C), np.int32)
        sample_pos = np.zeros((S, C), np.int64)
        lora_slots = None        # q-block -> adapter device slot
        if self._lora is not None:
            lora_slots = np.full(NQB, self._lora.store.null_slot,
                                 np.int32)

        flat = 0
        spec_rows = []           # (req, base, drafts)
        for req in decodes:
            r = req.row
            base = appended[req.id]          # length before this step
            w = self.cache.length(req.id) - base     # 1 + len(drafts)
            d = [int(t) for t in drafts.get(req.id, [])][:w - 1]
            seg = flat // BQ
            seq_ids[seg] = r
            q_starts[seg] = base
            q_valids[seg] = w
            if lora_slots is not None and req.adapter is not None:
                lora_slots[seg] = self._lora.store.slot_of(req.adapter)
            # feed = last committed token + the draft continuation
            ids[0, flat] = req.generated[-1]
            if d:
                ids[0, flat + 1:flat + w] = d
            slots[flat:flat + w] = self.cache.slot_mapping(
                req.id, base, w)
            positions[0, flat:flat + w] = np.arange(base, base + w)
            tables[r] = self.cache.block_table(req.id)
            ctx[r] = base + w
            for j in range(C):
                jj = min(j, w - 1)           # clamp unused columns
                last_index[r, j] = flat + jj
                sample_pos[r, j] = base + 1 + jj
            spec_rows.append((req, base, d))
            flat += BQ
        chunk_row = None
        if chunk is not None:
            req, start, n = chunk
            r = req.row
            ids[0, flat:flat + n] = req.prompt[start:start + n]
            slots[flat:flat + n] = self.cache.slot_mapping(
                req.id, start, n)
            positions[0, flat:flat + n] = np.arange(start, start + n)
            nseg = -(-n // BQ)
            for j in range(nseg):
                seq_ids[flat // BQ + j] = r
                q_starts[flat // BQ + j] = start + j * BQ
                q_valids[flat // BQ + j] = min(BQ, n - j * BQ)
            if lora_slots is not None and req.adapter is not None:
                lora_slots[flat // BQ:flat // BQ + nseg] = \
                    self._lora.store.slot_of(req.adapter)
            tables[r] = self.cache.block_table(req.id)
            ctx[r] = start + n
            if start + n == len(req.prompt):
                # prompt complete: sample the first new token (col 0)
                last_index[r, :] = flat + n - 1
                sample_pos[r, :] = start + n
                chunk_row = (r, req)
            flat += nseg * BQ

        self._packed_context = int(ctx.sum())
        self._view.set_inputs(slots, tables, ctx, positions, seq_ids,
                              q_starts, q_valids, last_index,
                              sample_pos)
        if lora_slots is not None:
            self._lora.stage(lora_slots)
        args = self._control_tensors(
            [self._rows[r] for r in range(S)], S)
        return self._tensor(ids), args, spec_rows, chunk_row

    def _control_tensors(self, reqs, n):
        """Per-row sampling controls; None entries are masked rows.
        Counts the step, and whether its sampler's filter will run: the
        predicate of `_sample_rows` on the same values."""
        seeds = np.zeros(n, np.int32)
        do_sample = np.zeros(n, bool)
        top_k = np.zeros(n, np.int32)
        top_p = np.ones(n, np.float32)
        temp = np.ones(n, np.float32)
        for i, req in enumerate(reqs):
            if req is None:
                continue
            seeds[i] = req.seed
            do_sample[i] = req.do_sample
            top_k[i] = req.top_k
            top_p[i] = req.top_p
            temp[i] = req.temperature
        filters = bool(np.any(do_sample & (temp > 0)))
        self._counters["sampler_steps"] += 1
        self._counters["sampler_filter_steps"] += filters
        if obs.enabled():
            obs.get_registry().counter(
                "sampler.filter_steps" if filters
                else "sampler.greedy_steps").inc()
        return tuple(self._tensor(a)
                     for a in (seeds, do_sample, top_k, top_p, temp))

    @staticmethod
    def _tensor(arr):
        return Tensor(jnp.asarray(arr), _internal=True,
                      stop_gradient=True)

    # -- committing + draining ------------------------------------------
    def _commit_token(self, req, token):
        """Append one accepted/drained token to ``req`` plus everything
        that hangs off a committed token: TTFT metrics, SLO charging,
        per-tenant accounting, streaming delivery, EOS/max-new cut."""
        if not req.generated and req.t_first_token is None:
            req.t_first_token = time.perf_counter()
            if req.t_submit is not None:
                ttft = (req.t_first_token - req.t_submit) * 1e3
                reg = obs.get_registry()
                reg.gauge("serving.ttft_ms").set(ttft)
                reg.histogram("serving.ttft_ms_hist").observe(ttft)
                if self.slo is not None:
                    self.slo.on_first_token(req, ttft)
        req.generated.append(token)
        self._tokens_generated += 1
        if self.slo is not None:
            self.slo.on_tokens(req, 1)
        if req.tenant:
            self._step_tenant_tokens[req.tenant] = \
                self._step_tenant_tokens.get(req.tenant, 0) + 1
        if (req.eos_token_id is not None
                and token == req.eos_token_id):
            req.done = True
        elif len(req.generated) >= req.max_new_tokens:
            req.done = True
        stream = self._streams.get(req.id)
        if stream is not None:
            # absolute completion index: stream_offset carries tokens a
            # requeue (preemption or failover replay) folded into the
            # prompt, so replayed commits dedup instead of re-delivering
            stream.put(token, req.stream_offset + len(req.generated) - 1,
                       finished=req.done)

    def _drain(self, lag):
        """Read dispatched token arrays older than ``lag`` steps back to
        the host — the only device synchronization in the loop."""
        if len(self._pending) <= lag:
            return
        with obs.span("engine:drain", boundary=True, lag=lag):
            while len(self._pending) > lag:
                rows_reqs, device_toks, reports = self._pending.pop(0)
                host = np.asarray(device_toks)
                if reports:
                    self._count_reports(reports)
                for idx, req in rows_reqs:
                    if req.done:
                        continue     # tokens raced past EOS: discard
                    self._commit_token(req, int(host[idx]))

    def _count_reports(self, reports):
        """A drained step's reports into the counters: the expert
        layers' plans (``moe_dispatch.plan_counters`` a layer; a sixth
        column counts the zero-compute experts' assignments)."""
        moe = np.asarray(reports["moe"])            # [layers, 5 or 6]
        self._count({name: int(moe[:, n].sum()) for n, name in (
            (0, "moe_assignments"), (1, "moe_experts_touched"),
            (2, "moe_plan_rows"), (4, "moe_assignments_routed"),
            (5, "moe_zero_assignments")) if n < moe.shape[1]})
        # the fullest expert of any layer of any step so far
        self._counters["moe_max_expert_rows"] = max(
            self._counters.get("moe_max_expert_rows", 0),
            int(moe[:, 3].max()))

    def _collect_finished(self):
        for req in list(self.scheduler.running):
            if req.done:
                if req.row is not None:
                    self._rows[req.row] = None
                self._lora_release(req)
                # same wall clock as t_first_token so per-request TPOT
                # ((t_finish - t_first_token) / (n-1)) is consistent
                req.t_finish = time.perf_counter()
                self.scheduler.finish(req)
                if self.proposer is not None:
                    self.proposer.drop(req.id)
                if self.slo is not None:
                    self.slo.on_finish(req)
                stream = self._streams.get(req.id)
                if stream is not None:
                    stream.close()
                self._results[req.id] = req
                self._step_finished.append(req)
