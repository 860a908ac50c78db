"""PipelineParallel: microbatched pipeline training over a `pp` mesh axis.

Reference parity: `fleet/meta_parallel/pipeline_parallel.py`
(PipelineParallel.train_batch 1F1B; interleaved variant;
pp_utils/p2p_communication.py send/recv between stage ranks) [UNVERIFIED —
empty reference mount].

TPU-native (SURVEY.md §2.3 PP row, §3.6): the per-rank P2P send/recv loop
becomes ONE compiled SPMD schedule (pp_utils/spmd_schedule.py):
stage-stacked parameters sharded over the `pp` mesh axis, a lax.scan over
GPipe ticks with `ppermute` inter-stage activation transfer, remat around
each stage body, and the optimizer update fused into the same executable.

When the model violates the SPMD formulation's constraints (heterogeneous
stages, fp16 GradScaler, tensor/sep parallel mixed in, no mesh), the
engine build fails and train_batch falls back to microbatch gradient
accumulation — same loss/grad math, no inter-stage parallelism — and says
so once in the log.
"""
from __future__ import annotations

import logging

import numpy as np

from ....core.tensor import Tensor
from ...parallel import DataParallel

logger = logging.getLogger("paddle_tpu.pipeline")

__all__ = ["PipelineParallel", "PipelineParallelWithInterleave"]


class PipelineParallel(DataParallel):
    def __init__(self, layers, hcg=None, strategy=None, **kwargs):
        super().__init__(layers)
        self._hcg = hcg
        self._strategy = strategy
        cfg = (strategy.pipeline_configs if strategy is not None else
               {"accumulate_steps": 1, "micro_batch_size": 1})
        self.accumulate_steps = int(cfg.get("accumulate_steps", 1))
        self.micro_batch_size = int(cfg.get("micro_batch_size", 1))
        self._pipeline_layer = layers  # a PipelineLayer
        self._engine = None       # SpmdPipelineEngine | False (fallback)

    def forward(self, *args, **kwargs):
        self._sync_from_engine()  # see the engine-trained weights
        return self._layers(*args, **kwargs)

    # ------------------------------------------------------------------
    def _try_build_engine(self, optimizer):
        if self._engine is not None:
            return
        hcg = self._hcg
        ok = (hcg is not None and getattr(hcg, "mesh", None) is not None
              and hcg.get_pipe_parallel_world_size() > 1
              and hasattr(self._pipeline_layer, "segment"))
        if ok:
            from ....optimizer.optimizer import Optimizer as _OptBase
            if type(optimizer)._pure_update is _OptBase._pure_update:
                logger.warning(
                    "pipeline: %s has no fused static update; falling "
                    "back to gradient accumulation",
                    type(optimizer).__name__)
                self._engine = False
                return
            # primary: global-array engine (heterogeneous stages, pp×mp,
            # GradScaler); secondary: shard_map GPipe (homogeneous, mp=1)
            try:
                from .pp_utils import GlobalPipelineEngine
                n_virtual = getattr(self, "_num_virtual_stages", 1)
                self._engine = GlobalPipelineEngine(
                    self._pipeline_layer, hcg, optimizer,
                    n_micro=max(self.accumulate_steps, 1),
                    remat=True, n_virtual=n_virtual)
                logger.info(
                    "pipeline: global-array GPipe engine over pp=%d, "
                    "%d microbatches, %d virtual stage(s)",
                    hcg.get_pipe_parallel_world_size(),
                    max(self.accumulate_steps, 1), n_virtual)
                return
            except Exception as e:
                logger.warning(
                    "pipeline: global engine unavailable (%s); trying "
                    "the shard_map engine", e)
            try:
                if (hcg.get_model_parallel_world_size() != 1
                        or hcg.get_sep_parallel_world_size() != 1):
                    raise ValueError("shard_map engine requires mp=1 "
                                     "and sep=1")
                from .pp_utils import SpmdPipelineEngine
                self._engine = SpmdPipelineEngine(
                    self._pipeline_layer, hcg, optimizer,
                    n_micro=max(self.accumulate_steps, 1),
                    remat=True)
                logger.info(
                    "pipeline: SPMD GPipe engine over pp=%d mesh axis, "
                    "%d microbatches",
                    hcg.get_pipe_parallel_world_size(),
                    max(self.accumulate_steps, 1))
                return
            except Exception as e:
                logger.warning(
                    "pipeline: SPMD engine unavailable (%s); falling back "
                    "to microbatch gradient accumulation (no inter-stage "
                    "parallelism)", e)
        else:
            logger.warning(
                "pipeline: no usable pp mesh; falling back to microbatch "
                "gradient accumulation")
        self._engine = False

    # ------------------------------------------------------------------
    def train_batch(self, data, optimizer, lr_scheduler=None, scaler=None):
        """Split into micro-batches and run the pipeline schedule."""
        use_scaler = scaler is not None and scaler.is_enable()
        # a scaler can only ride the global engine; once an attempt
        # showed this model builds a non-global engine, stop rebuilding
        # per scaler batch
        if not (use_scaler and self._engine is None
                and getattr(self, "_scaler_incompat", False)):
            self._try_build_engine(optimizer)
        engine = self._engine if self._engine not in (None, False) \
            else None
        if engine is not None and use_scaler and \
                not hasattr(engine, "outer"):
            self._scaler_incompat = True
            if engine._dirty:
                engine.sync_params_to_layers()
            # never retire permanently: a later scaler-free batch can
            # rebuild from the (current) eager params
            logger.warning(
                "pipeline: %s cannot serve a GradScaler; this batch "
                "runs on the accumulation path",
                type(engine).__name__)
            self._engine = None
            engine = None
        if engine is not None:
            inputs = data[0]
            n0 = (inputs.shape[0] if hasattr(inputs, "shape")
                  else len(inputs))
            if n0 % engine.n_micro == 0:
                return self._train_batch_spmd(data, optimizer,
                                              lr_scheduler, scaler)
            # ragged batch: the accumulation path trains the EAGER
            # params, so the engine's stacked copies must sync down and
            # the engine rebuilds later from the updated weights
            logger.warning(
                "pipeline: batch %d not divisible by accumulate_steps "
                "%d; running this batch on the accumulation path",
                n0, engine.n_micro)
            if engine._dirty:
                engine.sync_params_to_layers()
            self._engine = None
        return self._train_batch_accum(data, optimizer, lr_scheduler,
                                       scaler)

    def _train_batch_spmd(self, data, optimizer, lr_scheduler,
                          scaler=None):
        import jax.numpy as jnp

        inputs, labels = data
        x = inputs._value if isinstance(inputs, Tensor) else \
            jnp.asarray(np.asarray(inputs))
        y = labels._value if isinstance(labels, Tensor) else \
            jnp.asarray(np.asarray(labels))
        n_micro = self._engine.n_micro
        if x.shape[0] % n_micro != 0:
            raise ValueError(
                f"batch {x.shape[0]} not divisible by accumulate_steps "
                f"{n_micro}")
        xm = x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
        ym = y.reshape((n_micro, y.shape[0] // n_micro) + y.shape[1:])
        lr = optimizer.get_lr() if hasattr(optimizer, "get_lr") else 1e-3
        use_scaler = scaler is not None and scaler.is_enable()
        if use_scaler:
            loss, found_inf = self._engine.train_step(
                xm, ym, lr, scale=scaler._scale)
            # in-graph check_finite_and_unscale already gated the fused
            # update; the host just evolves the dynamic scale
            scaler._found_inf = found_inf
            scaler.update()
        else:
            loss = self._engine.train_step(xm, ym, lr)
            if isinstance(loss, tuple):
                loss = loss[0]
        if lr_scheduler is not None:
            lr_scheduler.step()
        return Tensor(jnp.asarray(loss, jnp.float32), _internal=True,
                      stop_gradient=True)

    def _train_batch_accum(self, data, optimizer, lr_scheduler=None,
                           scaler=None):
        from ....ops.manipulation import split

        # if the SPMD engine trained first, its stacked params are newer
        self._sync_from_engine()

        inputs, labels = data
        n_micro = self.accumulate_steps
        if n_micro > 1 and inputs.shape[0] % n_micro == 0:
            micro_in = split(inputs, n_micro, 0)
            micro_lab = split(labels, n_micro, 0)
        else:
            micro_in, micro_lab = [inputs], [labels]
            n_micro = 1

        total_loss = None
        for mi, ml in zip(micro_in, micro_lab):
            out = self._layers(mi) if not hasattr(
                self._layers, "run_function") else self._layers.forward(mi)
            loss_fn = getattr(self._pipeline_layer, "_loss_fn", None)
            loss = loss_fn(out, ml) if loss_fn is not None else out
            scaled = loss * (1.0 / n_micro)
            if scaler is not None:
                scaler.scale(scaled).backward()
            else:
                scaled.backward()
            total_loss = loss if total_loss is None else total_loss + loss
        if scaler is not None:
            scaler.step(optimizer)
        else:
            optimizer.step()
        optimizer.clear_grad()
        if lr_scheduler is not None:
            lr_scheduler.step()
        return total_loss * (1.0 / n_micro)

    # ------------------------------------------------------------------
    def _sync_from_engine(self):
        if self._engine not in (None, False):
            self._engine.sync_params_to_layers()

    def eval_batch(self, data, compute_loss=True):
        from ....core.autograd import no_grad

        self._sync_from_engine()
        inputs, labels = data
        with no_grad():
            out = self._layers.forward(inputs) if hasattr(
                self._layers, "run_function") else self._layers(inputs)
            loss_fn = getattr(self._pipeline_layer, "_loss_fn", None)
            if compute_loss and loss_fn is not None:
                return loss_fn(out, labels)
        return out

    def state_dict(self, *args, **kwargs):
        self._sync_from_engine()
        return super().state_dict(*args, **kwargs)


class PipelineParallelWithInterleave(PipelineParallel):
    """Interleaved (virtual-pipeline) variant.

    Reference parity: `fleet/meta_parallel/pipeline_parallel.py`
    PipelineParallelWithInterleave (Megatron virtual stages)
    [UNVERIFIED — empty reference mount; SURVEY.md:156].

    TPU-native redesign: the trunk is cut into pp*v chunks assigned
    ROUND-ROBIN (chunk c -> mesh slot c % pp, phase c // pp) and the
    global-array engine's scan computes ONE chunk per slot per tick —
    each slot's active chunk is selected by a per-(tick, slot) phase
    index that GATHERS the chunk's weights from a replicated (v, ...)
    dim of the pp-sharded parameter stack.  Selection over weights is
    data movement, not a serial loop over v chunks (and not a
    lax.switch, which under vmap would execute every branch), so a
    tick costs ~1/v of a full-stage tick and the schedule runs
    n_micro*v + pp - 1 ticks: the fill/drain bubble shrinks from
    (pp-1) full-stage ticks to (pp-1) chunk ticks — the Megatron
    bubble reduction, inside one compiled SPMD program.  See
    GlobalPipelineEngine(n_virtual=v); scripts/pp_memory_probe.py
    prints the bubble/memory table (XLA's memory analysis on virtual
    CPU devices).
    """

    def __init__(self, layers, hcg=None, strategy=None,
                 num_virtual_pipeline_stages=None, **kwargs):
        super().__init__(layers, hcg=hcg, strategy=strategy, **kwargs)
        # kwarg wins; else the PipelineLayer's own recorded request
        # (constructing this class directly must not silently drop the
        # layer's num_virtual_pipeline_stages)
        self._num_virtual_stages = int(
            num_virtual_pipeline_stages
            or getattr(layers, "_num_virtual_pipeline_stages", 1) or 1)
