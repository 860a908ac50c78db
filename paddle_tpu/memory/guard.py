"""The memory guard: pre-flight budget checks, structured runtime OOM
diagnosis, and the policy object that arms the degradation ladder.

Three knobs:

  PADDLE_TPU_MEMORY_GUARD   "off" → no pre-flight check, raw re-raise
                            unset/"1"/"on" → pre-flight HbmBudgetError +
                              runtime TpuOutOfMemoryError (the default)
                            "ladder" → additionally install a default
                              GuardPolicy so guarded entry points retry
                              through the degradation ladder
  PADDLE_TPU_HBM_BUDGET     per-device budget for CPU tests (bytes or
                            512M/8G form); on TPU the allocator's real
                            bytes_limit is used when unset
  PADDLE_TPU_FAULT_PLAN     an ``exec.oom:oom`` event makes every
                            guarded dispatch raise a synthetic
                            RESOURCE_EXHAUSTED — OOM is injectable and
                            replayable like any PR-1 fault

Executors call ``preflight_check()`` right after AOT compilation and run
dispatch under ``oom_context()``; models consult ``remat_enabled()`` so
the ladder's first rung can flip recompute on globally without touching
layer configs.
"""
from __future__ import annotations

import contextlib
import logging
import os
import threading

from .. import observability as obs
from .errors import HbmBudgetError, TpuOutOfMemoryError
from .estimator import analyze_compiled, check_budget, device_hbm_budget

__all__ = ["ENV_MEMORY_GUARD", "guard_enabled", "guard_mode", "GuardPolicy",
           "set_guard_policy", "get_guard_policy", "preflight_check",
           "oom_context", "is_oom_error", "remat_enabled", "set_remat",
           "remat_scope", "last_estimate", "record_estimate",
           "register_resident", "unregister_resident", "resident_items",
           "host_resident_items"]

ENV_MEMORY_GUARD = "PADDLE_TPU_MEMORY_GUARD"
OOM_SITE = "exec.oom"

logger = logging.getLogger("paddle_tpu.memory")

_state = threading.local()
_policy = None
_policy_lock = threading.Lock()


def guard_mode():
    """"off" | "on" | "ladder" from PADDLE_TPU_MEMORY_GUARD."""
    v = os.environ.get(ENV_MEMORY_GUARD, "on").strip().lower()
    if v in ("0", "off", "false", "no", "disable", "disabled"):
        return "off"
    if v == "ladder":
        return "ladder"
    return "on"


def guard_enabled():
    return guard_mode() != "off"


class GuardPolicy:
    """What the guard may do when a program does not fit.

    rungs: ordered degradation ladder, a subset of
    ("remat", "grad_accum", "halve_batch").  ladder.py interprets them;
    ``taken`` records (rung, detail) for every rung actually engaged so
    degraded runs are visibly degraded (also asserted in tests).
    """

    DEFAULT_RUNGS = ("remat", "grad_accum", "halve_batch")

    def __init__(self, rungs=None, micro_batches=2, min_batch=1):
        rungs = tuple(rungs if rungs is not None else self.DEFAULT_RUNGS)
        unknown = set(rungs) - set(self.DEFAULT_RUNGS)
        if unknown:
            raise ValueError(f"GuardPolicy: unknown rungs {sorted(unknown)} "
                             f"(choose from {self.DEFAULT_RUNGS})")
        self.rungs = rungs
        self.micro_batches = int(micro_batches)
        self.min_batch = int(min_batch)
        self.taken = []

    def record(self, rung, detail=""):
        self.taken.append((rung, detail))
        obs.instant("memory.ladder", cat="memory", rung=rung,
                    detail=detail)
        logger.warning("memory guard: degradation rung %r engaged%s",
                       rung, f" ({detail})" if detail else "")

    def __repr__(self):
        return (f"GuardPolicy(rungs={self.rungs}, "
                f"micro_batches={self.micro_batches}, "
                f"min_batch={self.min_batch}, taken={self.taken})")


def set_guard_policy(policy):
    """Install (or clear, with None) the global GuardPolicy."""
    global _policy
    with _policy_lock:
        _policy = policy
    return policy


def get_guard_policy():
    """The installed GuardPolicy; under PADDLE_TPU_MEMORY_GUARD=ladder a
    default one is created on first use."""
    global _policy
    if _policy is None and guard_mode() == "ladder":
        with _policy_lock:
            if _policy is None:
                _policy = GuardPolicy()
    return _policy


# -- remat hook (ladder rung 1) ------------------------------------------
_remat = {"on": False}


def remat_enabled():
    """True when the ladder (or a user) turned on global recompute.
    Transformer/GPT blocks consult this alongside their own
    use_recompute config, so the ladder can flip it without rebuilds."""
    return _remat["on"]


def set_remat(on):
    prev = _remat["on"]
    _remat["on"] = bool(on)
    return prev


@contextlib.contextmanager
def remat_scope(on=True):
    prev = set_remat(on)
    try:
        yield
    finally:
        set_remat(prev)


# -- process-wide resident buffers --------------------------------------
# Long-lived device allocations that are NOT arguments of the program
# being pre-flighted (the serving engine's paged KV-cache block pool is
# the canonical one) still occupy HBM while any program runs.  They
# register here as a named line item so every preflight charges them and
# HbmBudgetError reports e.g. "kv cache blocks" next to params/opt-state.
_residents = {}
#: host-RAM residents (the KV cache's spill ring is the canonical one):
#: named line items for triage that are NOT charged against the device
#: HBM preflight — host memory is not HBM
_host_residents = {}
_residents_lock = threading.Lock()


def register_resident(name, nbytes, buffer_ids=None, host=False):
    """Charge a long-lived device allocation against every future
    preflight.  ``buffer_ids`` is an optional zero-arg callable returning
    the current ``id()`` set of the backing jax arrays — when a program's
    own arguments include those buffers (the engine's decode step takes
    the pool as donated state, already counted in argument_bytes), the
    preflight skips the double charge but keeps the named line item.
    ``host=True`` registers a host-RAM allocation instead: it appears in
    ``host_resident_items()`` (and memory triage output) but never
    counts against the device budget."""
    with _residents_lock:
        if host:
            _host_residents[name] = int(nbytes)
        else:
            _residents[name] = (int(nbytes), buffer_ids)
    obs.instant("memory.resident", cat="memory", resident=name,
                nbytes=int(nbytes), host=bool(host))


def unregister_resident(name, host=False):
    with _residents_lock:
        if host:
            return _host_residents.pop(name, None) is not None
        return _residents.pop(name, None) is not None


def resident_items():
    """Snapshot [(name, nbytes, buffer_ids_fn)] of registered residents."""
    with _residents_lock:
        return [(n, b, f) for n, (b, f) in _residents.items()]


def host_resident_items():
    """Snapshot [(name, nbytes)] of registered HOST-RAM residents."""
    with _residents_lock:
        return list(_host_residents.items())


# -- estimates ----------------------------------------------------------
def record_estimate(estimate):
    """Remember the latest per-thread estimate (bench/reporting reads it
    back via last_estimate())."""
    _state.last = estimate
    return estimate


def last_estimate():
    return getattr(_state, "last", None)


def preflight_check(compiled, program="<program>", named_buffers=None,
                    budget=None, raise_on_over=True, pipeline_depth=1,
                    per_step_io_bytes=0, resident_skip_ids=None):
    """Estimate ``compiled``'s footprint and hold it to the HBM budget.

    Runs right after AOT compilation, before the first dispatch.  Returns
    the MemoryEstimate (None when the backend has no memory analysis or
    the guard is off).  Raises HbmBudgetError when over budget, unless
    ``raise_on_over=False`` (the ladder probes budgets that way).

    ``pipeline_depth`` > 1 (PADDLE_TPU_PIPELINE_DEPTH) charges the async
    step pipeline's in-flight buffers: each of the depth-1 extra
    un-synchronized steps keeps its outputs plus ``per_step_io_bytes``
    of feeds live, so the estimate covers the pipelined steady state,
    not just one isolated step.  An output that aliases a donated
    argument (a serving step's KV and state pools) is the next step's
    argument and exists once however many steps are in flight: only the
    outputs that alias nothing are charged.

    Registered residents (register_resident) are charged into
    ``est.resident_bytes`` and named in ``est.buffers`` — except when
    ``resident_skip_ids`` shows the resident's backing arrays are among
    this program's own arguments (already in argument_bytes).
    """
    if not guard_enabled():
        return None
    est = analyze_compiled(compiled, program=program,
                           named_buffers=named_buffers)
    if est is None:
        return None
    extra_steps = max(0, int(pipeline_depth) - 1)
    if extra_steps:
        est.pipeline_depth = int(pipeline_depth)
        est.pipeline_bytes = extra_steps * (
            max(0, est.output_bytes - est.alias_bytes)
            + int(per_step_io_bytes))
    skip = set(resident_skip_ids or ())
    for rname, rbytes, ids_fn in resident_items():
        est.buffers.append((rname, rbytes))
        try:
            rids = set(ids_fn() or ()) if ids_fn is not None else set()
        except Exception:
            rids = set()
        if not (skip and rids & skip):
            est.resident_bytes += rbytes
    record_estimate(est)
    if budget is None:
        budget = device_hbm_budget()
    obs.instant("memory.preflight", cat="memory", program=program,
                total_bytes=est.total_bytes, temp_bytes=est.temp_bytes,
                argument_bytes=est.argument_bytes,
                pipeline_bytes=est.pipeline_bytes, budget=budget)
    if raise_on_over:
        check_budget(est, budget=budget, site=OOM_SITE)
    return est


def is_oom_error(exc):
    """Does ``exc`` look like a device allocator failure?  Matches XLA's
    RESOURCE_EXHAUSTED status and the common out-of-memory phrasings
    (and therefore also the injected ``oom`` fault)."""
    if isinstance(exc, (HbmBudgetError, TpuOutOfMemoryError)):
        return False  # already structured; don't double-wrap
    msg = str(exc)
    return ("RESOURCE_EXHAUSTED" in msg
            or "Out of memory" in msg
            or "out of memory" in msg
            or "Resource exhausted" in msg)


@contextlib.contextmanager
def oom_context(program="<program>", estimate=None, device=None,
                site=OOM_SITE):
    """Run a device dispatch; re-raise allocator failures structured.

    The ``fault_point(site)`` probe is INSIDE the try so an injected
    ``oom`` event is caught and wrapped exactly like a real
    RESOURCE_EXHAUSTED — the ladder and the diagnosis path are testable
    on CPU.  With the guard off, errors pass through untouched.
    """
    from ..distributed.fault_tolerance.plan import fault_point
    try:
        fault_point(site)
        yield
    except Exception as e:
        if not guard_enabled() or not is_oom_error(e):
            raise
        if estimate is None:
            estimate = last_estimate()
        from ..device import memory_stats
        try:
            stats = memory_stats(device)
        except Exception:
            stats = {}
        top = estimate.top_buffers(5) if estimate is not None else ()
        obs.instant("memory.oom", cat="memory", program=program,
                    site=site, error=str(e)[:200])
        raise TpuOutOfMemoryError(
            str(e), program=program, estimate=estimate,
            budget=device_hbm_budget(device), top_buffers=top,
            stats=stats, site=site) from e
