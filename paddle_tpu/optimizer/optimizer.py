"""Optimizers (paddle.optimizer parity) with fused multi-tensor updates.

Reference parity: `python/paddle/optimizer/optimizer.py`, `adamw.py` → phi
`gpu/adamw_kernel.cu` multi-tensor path [UNVERIFIED — empty reference
mount].

TPU-native: ``step()`` performs ONE dispatch over all parameters (flat
lists in, flat lists out) so the whole optimizer compiles to a single fused
XLA program — the multi_tensor_adam equivalent, and under
``paddle.jit.to_static`` the update fuses into the train-step executable.
The learning rate rides in a Tensor so schedulers don't retrigger
compilation.
"""
from __future__ import annotations

import threading
from collections import OrderedDict

import numpy as np
import jax.numpy as jnp

from ..core import autograd
from ..observability.timeline import span as _span
from ..core.dispatch import dispatch
from ..core.tensor import Tensor, to_tensor
from .lr import LRScheduler

__all__ = ["Optimizer", "SGD", "Momentum", "Adam", "AdamW", "Adamax",
           "Adagrad", "Adadelta", "RMSProp", "Lamb",
           "register_pre_step_hook", "run_pre_step_hooks"]

# Pre-step hooks: callables(optimizer, params) run at the top of every
# step() — the fault-tolerance layer's seam (gradient poisoning under a
# FaultPlan, NaN sentinels) without the optimizer importing any of it.
_pre_step_hooks = []
_hooks_ran = threading.local()


def register_pre_step_hook(fn):
    """Register ``fn(optimizer, params)`` to run before each update.
    Returns a zero-arg remover."""
    _pre_step_hooks.append(fn)

    def remove():
        try:
            _pre_step_hooks.remove(fn)
        except ValueError:
            pass
    return remove


def run_pre_step_hooks(optimizer, params):
    """Run the hooks ahead of step() — sentinels (amp.debugging.
    skip_step_on_nonfinite) call this so injected faults land BEFORE
    their gradient check; the immediately-following step() won't run
    the hooks a second time."""
    for hook in _pre_step_hooks:
        hook(optimizer, params)
    _hooks_ran.flag = True


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay=None, grad_clip=None, name=None,
                 multi_precision=False):
        self._learning_rate = learning_rate
        self._lr_tensor = to_tensor(float(self._current_lr()),
                                    dtype="float32")
        self._lr_tensor.name = "learning_rate"
        self._lr_tensor.persistable = True
        if parameters is not None:
            parameters = list(parameters)
        self._parameter_list = parameters
        self._param_groups = None
        if parameters and isinstance(parameters[0], dict):
            self._param_groups = parameters
            flat = []
            for g in parameters:
                flat.extend(g["params"])
            self._parameter_list = flat
        self._weight_decay = weight_decay
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._accumulators = OrderedDict()  # name -> {param_name: Tensor}
        self._step_count = to_tensor(0, dtype="int64")
        self._step_count.persistable = True
        self._master_weights = {}

    # ---- lr handling ----
    def _current_lr(self):
        if isinstance(self._learning_rate, LRScheduler):
            return self._learning_rate()
        return float(self._learning_rate)

    def get_lr(self):
        return self._current_lr()

    def set_lr(self, value):
        self._learning_rate = float(value)
        self._lr_tensor._inplace_update(
            jnp.asarray(value, jnp.float32))

    def set_lr_scheduler(self, scheduler):
        self._learning_rate = scheduler

    def _sync_lr(self):
        lr = self._current_lr()
        # skip the per-step h2d transfer (and, under lazy mode, a
        # spurious leaf-signature change) while the lr is unchanged —
        # the common case for constant-lr training
        if lr == getattr(self, "_lr_last", None):
            return
        self._lr_last = lr
        self._lr_tensor._inplace_update(
            jnp.asarray(lr, jnp.float32))

    # ---- accumulators ----
    def _acc(self, name, param, init=0.0, shape=None, dtype=None):
        d = self._accumulators.setdefault(name, {})
        if param.name not in d:
            v = jnp.full(shape if shape is not None else param._value.shape,
                         init,
                         dtype if dtype is not None else (
                             jnp.float32 if param._value.dtype in
                             (jnp.bfloat16, jnp.float16)
                             else param._value.dtype))
            t = Tensor(v, _internal=True)
            t.name = f"{param.name}_{name}"
            t.persistable = True
            d[param.name] = t
        return d[param.name]

    def _params_with_grad(self):
        out = []
        for p in (self._parameter_list or []):
            if p.grad is not None and not p.stop_gradient:
                out.append(p)
        return out

    # ---- main API ----
    @autograd.no_grad()
    def step(self):
        with _span("opt:step", boundary=True):
            self._step()

    def _step(self):
        self._sync_lr()
        params = self._params_with_grad()
        if not params:
            return
        if getattr(_hooks_ran, "flag", False):
            _hooks_ran.flag = False  # sentinel already ran them
        else:
            for hook in _pre_step_hooks:
                hook(self, params)
        if getattr(self, "_skip_apply", False):
            # a hook (gradient accumulation, skip-step sentinel) asked
            # this step() to be a no-op: keep accumulated grads AND the
            # step counter untouched (Adam bias correction must count
            # applied updates only)
            self._skip_apply = False
            return
        if self._grad_clip is not None:
            self._grad_clip(params)
        l1 = self._l1_coeff()
        if l1:
            # L1Decay: g += coeff * sign(p), post-clip like the
            # reference's append_regularization_ops ordering (step()
            # already runs under no_grad)
            for p in params:
                p.grad = p.grad + l1 * p.detach().sign()
        self._apply(params)
        self._step_count._inplace_update(self._step_count._value + 1)

    def _apply(self, params):
        raise NotImplementedError

    def clear_grad(self, set_to_zero=False):
        for p in (self._parameter_list or []):
            p.clear_grad(set_to_zero)

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        from ..static.framework import Variable, in_static_mode, \
            default_main_program

        # `parameters`/`no_grad_set` restrict the update set for THIS
        # call only (paddle semantics); the constructor list must not be
        # permanently overwritten by one minimize() invocation.
        scoped = self._parameter_list
        if parameters is not None:
            scoped = list(parameters)
        if no_grad_set:
            excl = {id(t) for t in no_grad_set}
            if scoped:
                scoped = [p for p in scoped if id(p) not in excl]
            else:
                # no explicit list ("all trainables"): record the
                # exclusion for the Executor's update-set selection —
                # an empty _parameter_list would read as "no
                # restriction" there and as "update nothing" in eager
                self._no_grad_ids = (
                    getattr(self, "_no_grad_ids", set()) | excl)
        if in_static_mode() and isinstance(loss, Variable):
            # static graph: attach to the program; Executor lowers
            # forward+grad+update into one XLA executable.
            prog = default_main_program()
            prog._optimize_info = (self, loss)
            prog._loss_var = loss
            if scoped is not self._parameter_list:
                prog._minimize_params = list(scoped)
            return None, None
        loss.backward()
        if scoped is not self._parameter_list:
            prev, self._parameter_list = self._parameter_list, scoped
            try:
                self.step()
            finally:
                self._parameter_list = prev
        else:
            self.step()
        return None, None

    # ---- static-graph path (used by static.Executor) ----
    def _ensure_static_state(self, params):
        """Materialize accumulators for `params`; returns the flat state
        Tensor list in the layout `_pure_update` expects."""
        self._sync_lr()
        return self._static_state(params)

    def _static_state(self, params):
        return []

    def _clip_static_grads(self, grads):
        """Apply this optimizer's grad_clip in traced code (shared by
        the direct static path and meta-optimizer wrappers)."""
        if self._grad_clip is None:
            return grads
        from ..nn.clip import ClipGradByGlobalNorm, ClipGradByNorm, \
            ClipGradByValue
        if isinstance(self._grad_clip, ClipGradByGlobalNorm):
            total = jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in grads))
            cn = self._grad_clip.clip_norm
            scale = cn / jnp.maximum(total, cn)
            return tuple((g.astype(jnp.float32) * scale).astype(g.dtype)
                         for g in grads)
        if isinstance(self._grad_clip, ClipGradByNorm):
            cn = self._grad_clip.clip_norm
            out = []
            for g in grads:
                n = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
                s = cn / jnp.maximum(n, cn)
                out.append((g.astype(jnp.float32) * s).astype(g.dtype))
            return tuple(out)
        if isinstance(self._grad_clip, ClipGradByValue):
            return tuple(jnp.clip(g, self._grad_clip.min,
                                  self._grad_clip.max) for g in grads)
        return grads

    def _static_update(self, param_vals, grads, opt_vals, params,
                       lr=None, step=None):
        # `lr` and `step` are traced per-step values when the caller
        # threads them as executable arguments (Executor/DistModel/the
        # pipeline engine do).  Baking them at trace time would freeze
        # an LRScheduler's changes AND Adam/AdamW's bias correction
        # (`1 - beta**step`) at the first step's values for the whole
        # cached-executable lifetime.
        if lr is None:
            lr = self._lr_tensor._value
        if step is None:
            step = self._step_count._value
            # advance the counter host-side (numpy): this runs while
            # TRACING the compiled step, and any jnp op here (even
            # asarray) would be lifted into the trace, leaking a tracer
            # into the eager step counter (it then poisons
            # optimizer.state_dict()).
            self._step_count._inplace_update(np.asarray(step) + 1)
        grads = self._clip_static_grads(grads)
        grads = self._l1_grads(grads, param_vals)
        return self._pure_update(lr, step, param_vals, grads, opt_vals,
                                 params)

    def _pure_update(self, lr, step, param_vals, grads, opt_vals, params):
        raise NotImplementedError(
            f"{type(self).__name__} does not support static-graph mode yet")

    # ---- state dict ----
    def state_dict(self):
        out = {}
        for acc_name, d in self._accumulators.items():
            for pname, t in d.items():
                out[f"{pname}_{acc_name}"] = t
        if isinstance(self._learning_rate, LRScheduler):
            out["LR_Scheduler"] = self._learning_rate.state_dict()
        out["global_step"] = self._step_count
        # auto-generated parameter names are session-counter dependent;
        # recording the save-time order lets set_state_dict map state
        # POSITIONALLY onto a freshly-built optimizer whose names differ
        out["__param_names__"] = [
            p.name for p in (self._parameter_list or [])
            if not p.stop_gradient]
        return out

    def set_state_dict(self, state_dict):
        def _val(src):
            return src._value if isinstance(src, Tensor) else \
                jnp.asarray(np.asarray(src))

        saved_names = state_dict.get("__param_names__")
        if saved_names is not None:
            # positional mapping: saved param i ↔ current param i; the
            # accumulator is MATERIALIZED via _acc so a fresh optimizer
            # (empty _accumulators) restores correctly
            cur = [p for p in (self._parameter_list or [])
                   if not p.stop_gradient]
            by_len = sorted(saved_names, key=len, reverse=True)
            pos = {n: i for i, n in enumerate(saved_names)}
            for key, src in state_dict.items():
                if key in ("LR_Scheduler", "global_step",
                           "__param_names__"):
                    continue
                for n in by_len:  # longest prefix wins (names nest)
                    if key.startswith(n + "_"):
                        i = pos[n]
                        if i < len(cur):
                            acc_name = key[len(n) + 1:]
                            t = self._acc(acc_name, cur[i])
                            t._inplace_update(jnp.asarray(
                                _val(src), t._value.dtype))
                        break
        else:  # legacy dicts: name-matched into existing accumulators
            for acc_name, d in self._accumulators.items():
                for pname in d:
                    key = f"{pname}_{acc_name}"
                    if key in state_dict:
                        d[pname]._inplace_update(jnp.asarray(
                            _val(state_dict[key]),
                            d[pname]._value.dtype))
        if "LR_Scheduler" in state_dict and isinstance(
                self._learning_rate, LRScheduler):
            self._learning_rate.set_state_dict(state_dict["LR_Scheduler"])
        if "global_step" in state_dict:
            src = state_dict["global_step"]
            v = src._value if isinstance(src, Tensor) else \
                jnp.asarray(src)
            self._step_count._inplace_update(v)

    set_dict = set_state_dict

    def _decay_coeff(self):
        """L2 coefficient for the per-optimizer `g + wd*p` decay term.
        L1Decay returns 0.0 here — its `coeff*sign(p)` term is added to
        the gradients at the two common points (step /_static_update),
        not per-optimizer (it used to silently apply as L2)."""
        wd = self._weight_decay
        if wd is None:
            return 0.0
        from ..regularizer import L1Decay
        if isinstance(wd, L1Decay):
            return 0.0
        if hasattr(wd, "_coeff"):
            return float(wd._coeff)
        return float(wd)

    def _l1_coeff(self):
        from ..regularizer import L1Decay
        wd = self._weight_decay
        return float(wd._coeff) if isinstance(wd, L1Decay) else 0.0

    def _l1_grads(self, grads, param_vals):
        """Traced L1Decay term: g += coeff*sign(p).  Shared by every
        traced update entry point that bypasses _static_update (the
        pipeline schedules call _pure_update directly)."""
        l1 = self._l1_coeff()
        if not l1:
            return grads
        return tuple(
            (g.astype(jnp.float32)
             + l1 * jnp.sign(pv.astype(jnp.float32))).astype(g.dtype)
            for g, pv in zip(grads, param_vals))


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay=None, grad_clip=None, name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)

    def _pure_update(self, lr, step, param_vals, grads, opt_vals, params):
        wd = self._decay_coeff()
        new_p = tuple(
            (p.astype(jnp.float32) - lr * (
                g.astype(jnp.float32) + wd * p.astype(jnp.float32))
             ).astype(p.dtype)
            for p, g in zip(param_vals, grads))
        return new_p, opt_vals

    def _apply(self, params):
        wd = self._decay_coeff()

        def impl(lr, *pg, wd, n):
            ps, gs = pg[:n], pg[n:]
            out = []
            for p, g in zip(ps, gs):
                g = g.astype(jnp.float32) + wd * p.astype(jnp.float32)
                out.append((p.astype(jnp.float32) -
                            lr * g).astype(p.dtype))
            return tuple(out)

        grads = [p.grad for p in params]
        outs = dispatch("sgd", impl, (self._lr_tensor,) + tuple(params) +
                        tuple(grads), dict(wd=wd, n=len(params)),
                        differentiable=False)
        for p, new in zip(params, outs):
            p._inplace_update(new._value)


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _static_state(self, params):
        return [self._acc("velocity", p) for p in params]

    def _pure_update(self, lr, step, param_vals, grads, opt_vals, params):
        wd = self._decay_coeff()
        mu = float(self._momentum)
        new_p, new_v = [], []
        for p, g, v in zip(param_vals, grads, opt_vals):
            gf = g.astype(jnp.float32) + wd * p.astype(jnp.float32)
            v2 = mu * v + gf
            upd = gf + mu * v2 if self._nesterov else v2
            new_p.append((p.astype(jnp.float32) - lr * upd).astype(p.dtype))
            new_v.append(v2)
        return tuple(new_p), tuple(new_v)

    def _apply(self, params):
        wd = self._decay_coeff()
        vels = [self._acc("velocity", p) for p in params]

        def impl(lr, *pgv, mu, wd, nesterov, n):
            ps, gs, vs = pgv[:n], pgv[n:2 * n], pgv[2 * n:]
            new_p, new_v = [], []
            for p, g, v in zip(ps, gs, vs):
                g = g.astype(jnp.float32) + wd * p.astype(jnp.float32)
                v2 = mu * v + g
                if nesterov:
                    upd = g + mu * v2
                else:
                    upd = v2
                new_p.append((p.astype(jnp.float32) -
                              lr * upd).astype(p.dtype))
                new_v.append(v2)
            return tuple(new_p) + tuple(new_v)

        grads = [p.grad for p in params]
        outs = dispatch("momentum", impl,
                        (self._lr_tensor,) + tuple(params) + tuple(grads) +
                        tuple(vels),
                        dict(mu=float(self._momentum), wd=wd,
                             nesterov=self._nesterov, n=len(params)),
                        differentiable=False)
        n = len(params)
        for p, new in zip(params, outs[:n]):
            p._inplace_update(new._value)
        for v, new in zip(vels, outs[n:]):
            v._inplace_update(new._value)


class _AdamBase(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, lazy_mode=False,
                 multi_precision=False, use_multi_tensor=True,
                 decoupled=False, apply_decay_param_fun=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._decoupled = decoupled
        self._apply_decay_param_fun = apply_decay_param_fun

    def _static_state(self, params):
        return ([self._acc("moment1", p) for p in params] +
                [self._acc("moment2", p) for p in params])

    def _pure_update(self, lr, step, param_vals, grads, opt_vals, params):
        n = len(param_vals)
        ms, vs = opt_vals[:n], opt_vals[n:]
        wd = self._decay_coeff()
        b1 = self._beta1() if callable(self._beta1) else float(self._beta1)
        b2 = self._beta2() if callable(self._beta2) else float(self._beta2)
        eps = float(self._epsilon)
        tf = (step + 1).astype(jnp.float32)
        bc1 = 1.0 - jnp.power(b1, tf)
        bc2 = 1.0 - jnp.power(b2, tf)
        new_p, new_m, new_v = [], [], []
        for p, g, m_, v_ in zip(param_vals, grads, ms, vs):
            pf = p.astype(jnp.float32)
            gf = g.astype(jnp.float32)
            if not self._decoupled and wd != 0.0:
                gf = gf + wd * pf
            m2 = b1 * m_ + (1 - b1) * gf
            v2 = b2 * v_ + (1 - b2) * gf * gf
            upd = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
            if self._decoupled and wd != 0.0:
                upd = upd + wd * pf
            new_p.append((pf - lr * upd).astype(p.dtype))
            new_m.append(m2)
            new_v.append(v2)
        return tuple(new_p), tuple(new_m) + tuple(new_v)

    def _apply(self, params):
        wd = self._decay_coeff()
        m = [self._acc("moment1", p) for p in params]
        v = [self._acc("moment2", p) for p in params]
        decay_mask = tuple(
            1.0 if (self._apply_decay_param_fun is None or
                    self._apply_decay_param_fun(p.name)) and
            getattr(p, "no_weight_decay", False) is False else 0.0
            for p in params)
        b1 = self._beta1() if callable(self._beta1) else float(self._beta1)
        b2 = self._beta2() if callable(self._beta2) else float(self._beta2)

        def impl(lr, t, *pgmv, b1, b2, eps, wd, decoupled, n, mask):
            ps, gs = pgmv[:n], pgmv[n:2 * n]
            ms, vs = pgmv[2 * n:3 * n], pgmv[3 * n:]
            tf = (t + 1).astype(jnp.float32)
            bc1 = 1.0 - jnp.power(b1, tf)
            bc2 = 1.0 - jnp.power(b2, tf)
            new_p, new_m, new_v = [], [], []
            for p, g, m_, v_, dm in zip(ps, gs, ms, vs, mask):
                pf = p.astype(jnp.float32)
                gf = g.astype(jnp.float32)
                if not decoupled and wd != 0.0:
                    gf = gf + wd * dm * pf
                m2 = b1 * m_ + (1 - b1) * gf
                v2 = b2 * v_ + (1 - b2) * gf * gf
                mhat = m2 / bc1
                vhat = v2 / bc2
                upd = mhat / (jnp.sqrt(vhat) + eps)
                if decoupled and wd != 0.0:
                    upd = upd + wd * dm * pf
                new_p.append((pf - lr * upd).astype(p.dtype))
                new_m.append(m2)
                new_v.append(v2)
            return tuple(new_p) + tuple(new_m) + tuple(new_v)

        grads = [p.grad for p in params]
        outs = dispatch(
            "adamw" if self._decoupled else "adam", impl,
            (self._lr_tensor, self._step_count) + tuple(params) +
            tuple(grads) + tuple(m) + tuple(v),
            dict(b1=b1, b2=b2, eps=float(self._epsilon), wd=wd,
                 decoupled=self._decoupled, n=len(params), mask=decay_mask),
            differentiable=False)
        n = len(params)
        for p, new in zip(params, outs[:n]):
            p._inplace_update(new._value)
        for t, new in zip(m, outs[n:2 * n]):
            t._inplace_update(new._value)
        for t, new in zip(v, outs[2 * n:]):
            t._inplace_update(new._value)


class Adam(_AdamBase):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, **kw):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, decoupled=False, **kw)


class AdamW(_AdamBase):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 **kw):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, decoupled=True,
                         apply_decay_param_fun=apply_decay_param_fun, **kw)


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _apply(self, params):
        m = [self._acc("moment", p) for p in params]
        u = [self._acc("inf_norm", p) for p in params]

        def impl(lr, t, *pgmu, b1, b2, eps, n):
            ps, gs = pgmu[:n], pgmu[n:2 * n]
            ms, us = pgmu[2 * n:3 * n], pgmu[3 * n:]
            tf = (t + 1).astype(jnp.float32)
            bc1 = 1.0 - jnp.power(b1, tf)
            outs_p, outs_m, outs_u = [], [], []
            for p, g, m_, u_ in zip(ps, gs, ms, us):
                gf = g.astype(jnp.float32)
                m2 = b1 * m_ + (1 - b1) * gf
                u2 = jnp.maximum(b2 * u_, jnp.abs(gf))
                upd = m2 / bc1 / (u2 + eps)
                outs_p.append((p.astype(jnp.float32) -
                               lr * upd).astype(p.dtype))
                outs_m.append(m2)
                outs_u.append(u2)
            return tuple(outs_p) + tuple(outs_m) + tuple(outs_u)

        grads = [p.grad for p in params]
        outs = dispatch("adamax", impl,
                        (self._lr_tensor, self._step_count) + tuple(params) +
                        tuple(grads) + tuple(m) + tuple(u),
                        dict(b1=float(self._beta1), b2=float(self._beta2),
                             eps=float(self._epsilon), n=len(params)),
                        differentiable=False)
        n = len(params)
        for p, new in zip(params, outs[:n]):
            p._inplace_update(new._value)
        for t, new in zip(m, outs[n:2 * n]):
            t._inplace_update(new._value)
        for t, new in zip(u, outs[2 * n:]):
            t._inplace_update(new._value)


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None,
                 initial_accumulator_value=0.0, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _apply(self, params):
        acc = [self._acc("moment", p, self._init_acc) for p in params]
        wd = self._decay_coeff()

        def impl(lr, *pga, eps, wd, n):
            ps, gs, accs = pga[:n], pga[n:2 * n], pga[2 * n:]
            outs_p, outs_a = [], []
            for p, g, a in zip(ps, gs, accs):
                gf = g.astype(jnp.float32) + wd * p.astype(jnp.float32)
                a2 = a + gf * gf
                outs_p.append((p.astype(jnp.float32) -
                               lr * gf / (jnp.sqrt(a2) + eps)).astype(
                                   p.dtype))
                outs_a.append(a2)
            return tuple(outs_p) + tuple(outs_a)

        grads = [p.grad for p in params]
        outs = dispatch("adagrad", impl,
                        (self._lr_tensor,) + tuple(params) + tuple(grads) +
                        tuple(acc),
                        dict(eps=float(self._epsilon), wd=wd,
                             n=len(params)), differentiable=False)
        n = len(params)
        for p, new in zip(params, outs[:n]):
            p._inplace_update(new._value)
        for t, new in zip(acc, outs[n:]):
            t._inplace_update(new._value)


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._epsilon, self._rho = epsilon, rho

    def _apply(self, params):
        avg_sq = [self._acc("avg_squared_grad", p) for p in params]
        avg_up = [self._acc("avg_squared_update", p) for p in params]
        wd = self._decay_coeff()

        def impl(lr, *arrs, eps, rho, wd, n):
            ps, gs = arrs[:n], arrs[n:2 * n]
            sqs, ups = arrs[2 * n:3 * n], arrs[3 * n:]
            outs_p, outs_s, outs_u = [], [], []
            for p, g, s, u in zip(ps, gs, sqs, ups):
                gf = g.astype(jnp.float32) + wd * p.astype(jnp.float32)
                s2 = rho * s + (1 - rho) * gf * gf
                upd = jnp.sqrt(u + eps) / jnp.sqrt(s2 + eps) * gf
                u2 = rho * u + (1 - rho) * upd * upd
                outs_p.append((p.astype(jnp.float32) -
                               lr * upd).astype(p.dtype))
                outs_s.append(s2)
                outs_u.append(u2)
            return tuple(outs_p) + tuple(outs_s) + tuple(outs_u)

        grads = [p.grad for p in params]
        outs = dispatch("adadelta", impl,
                        (self._lr_tensor,) + tuple(params) + tuple(grads) +
                        tuple(avg_sq) + tuple(avg_up),
                        dict(eps=float(self._epsilon), rho=float(self._rho),
                             wd=wd, n=len(params)), differentiable=False)
        n = len(params)
        for p, new in zip(params, outs[:n]):
            p._inplace_update(new._value)
        for t, new in zip(avg_sq, outs[n:2 * n]):
            t._inplace_update(new._value)
        for t, new in zip(avg_up, outs[2 * n:]):
            t._inplace_update(new._value)


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _apply(self, params):
        ms = [self._acc("mean_square", p) for p in params]
        mom = [self._acc("momentum", p) for p in params]
        mg = [self._acc("mean_grad", p) for p in params]
        wd = self._decay_coeff()

        def impl(lr, *arrs, rho, eps, mu, centered, wd, n):
            ps, gs = arrs[:n], arrs[n:2 * n]
            mss, moms, mgs = arrs[2 * n:3 * n], arrs[3 * n:4 * n], \
                arrs[4 * n:]
            o_p, o_ms, o_mom, o_mg = [], [], [], []
            for p, g, s, v, a in zip(ps, gs, mss, moms, mgs):
                gf = g.astype(jnp.float32) + wd * p.astype(jnp.float32)
                s2 = rho * s + (1 - rho) * gf * gf
                if centered:
                    a2 = rho * a + (1 - rho) * gf
                    denom = jnp.sqrt(s2 - a2 * a2 + eps)
                else:
                    a2 = a
                    denom = jnp.sqrt(s2 + eps)
                v2 = mu * v + lr * gf / denom
                o_p.append((p.astype(jnp.float32) - v2).astype(p.dtype))
                o_ms.append(s2)
                o_mom.append(v2)
                o_mg.append(a2)
            return tuple(o_p) + tuple(o_ms) + tuple(o_mom) + tuple(o_mg)

        grads = [p.grad for p in params]
        outs = dispatch("rmsprop", impl,
                        (self._lr_tensor,) + tuple(params) + tuple(grads) +
                        tuple(ms) + tuple(mom) + tuple(mg),
                        dict(rho=float(self._rho), eps=float(self._epsilon),
                             mu=float(self._momentum),
                             centered=self._centered, wd=wd, n=len(params)),
                        differentiable=False)
        n = len(params)
        for p, new in zip(params, outs[:n]):
            p._inplace_update(new._value)
        for t, new in zip(ms, outs[n:2 * n]):
            t._inplace_update(new._value)
        for t, new in zip(mom, outs[2 * n:3 * n]):
            t._inplace_update(new._value)
        for t, new in zip(mg, outs[3 * n:]):
            t._inplace_update(new._value)


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, parameters=None,
                 grad_clip=None, exclude_from_weight_decay_fn=None, **kw):
        super().__init__(learning_rate, parameters, None, grad_clip)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _apply(self, params):
        m = [self._acc("moment1", p) for p in params]
        v = [self._acc("moment2", p) for p in params]
        mask = tuple(0.0 if (self._exclude_fn and self._exclude_fn(p))
                     else 1.0 for p in params)

        def impl(lr, t, *arrs, b1, b2, eps, wd, n, mask):
            ps, gs = arrs[:n], arrs[n:2 * n]
            ms, vs = arrs[2 * n:3 * n], arrs[3 * n:]
            tf = (t + 1).astype(jnp.float32)
            bc1 = 1.0 - jnp.power(b1, tf)
            bc2 = 1.0 - jnp.power(b2, tf)
            o_p, o_m, o_v = [], [], []
            for p, g, m_, v_, dm in zip(ps, gs, ms, vs, mask):
                pf = p.astype(jnp.float32)
                gf = g.astype(jnp.float32)
                m2 = b1 * m_ + (1 - b1) * gf
                v2 = b2 * v_ + (1 - b2) * gf * gf
                r = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps) + \
                    wd * dm * pf
                w_norm = jnp.linalg.norm(pf)
                r_norm = jnp.linalg.norm(r)
                ratio = jnp.where((w_norm > 0) & (r_norm > 0),
                                  w_norm / r_norm, 1.0)
                o_p.append((pf - lr * ratio * r).astype(p.dtype))
                o_m.append(m2)
                o_v.append(v2)
            return tuple(o_p) + tuple(o_m) + tuple(o_v)

        grads = [p.grad for p in params]
        outs = dispatch("lamb", impl,
                        (self._lr_tensor, self._step_count) + tuple(params) +
                        tuple(grads) + tuple(m) + tuple(v),
                        dict(b1=float(self._beta1), b2=float(self._beta2),
                             eps=float(self._epsilon),
                             wd=float(self._lamb_wd), n=len(params),
                             mask=mask), differentiable=False)
        n = len(params)
        for p, new in zip(params, outs[:n]):
            p._inplace_update(new._value)
        for t, new in zip(m, outs[n:2 * n]):
            t._inplace_update(new._value)
        for t, new in zip(v, outs[2 * n:]):
            t._inplace_update(new._value)
