"""Elastic preemption-tolerant training (detection -> shrink -> resume).

PAPER.md's target is a training run on a *preemptible* v5p pod;
upstream Paddle ships a whole ``fleet/elastic`` tier for the same
reason.  This module closes the training-side loop the serving tier got
in PR 12:

detection
    Health probes at every step boundary: the ``dist.device_lost.<rank>``
    / ``dist.host_preempt`` fault sites, :class:`ElasticManager`
    ``dead_ranks()`` heartbeat staleness, and
    :class:`CollectiveTimeoutError` from the collective watchdog all
    escalate into one structured :class:`DeviceLostError`.  The step
    aborts cleanly: the pipeline ``InFlightWindow`` is drained (no
    leaked in-flight buffers) and the snapshot staging line item is
    released from the memory guard.

mesh-shrink recovery
    :meth:`MeshPlan.shrink` rebuilds the plan over the surviving
    devices — dp drops to the largest divisor that fits (so global
    batch stays divisible and resume is bit-identical), model-parallel
    axes that no longer fit fall back to replication with a TPU505
    finding.  The shrunk plan carries a bumped ``_generation`` inside
    ``cache_token()``, so executor/trace caches compile fresh instead
    of poisoning (or reusing) pre-loss entries.

async snapshot checkpointing
    At a step boundary the trainer captures a device->host copy of the
    training state (params, optimizer accumulators, step counter) —
    charged to the memory guard as a HOST line item — and a background
    thread writes it through the PR 1 tmp+rename+sha256-manifest path.
    The manifest's ``"train"`` block records ``step``, the RNG key, and
    the data-loader cursor.

deterministic resume
    Restore re-places every tensor under the shrunk plan via
    :func:`make_shard_and_gather_fns`, restores the RNG key and step
    counter from the manifest, and resumes the feed callback at the
    recorded cursor — bit-identical to a clean run started from the
    same checkpoint on the shrunk mesh (the chaos drill asserts it).

Observability: ``elastic.restarts`` / ``elastic.lost_steps`` counters,
an ``elastic.mttr_ms`` histogram, and ``recovery`` / ``ckpt`` timeline
lanes folded into ``phase_breakdown()``.
"""
from __future__ import annotations

import json
import os
import pickle
import shutil
import threading
import time

import numpy as np

from .. import observability as obs
from ..core import pipeline as _pipeline
from ..memory.guard import register_resident, unregister_resident
from .auto_parallel.sharding import (get_mesh_plan,
                                     make_shard_and_gather_fns,
                                     set_mesh_plan)
from .fault_tolerance.atomic import (MANIFEST_NAME, atomic_write,
                                     validate_checkpoint, write_manifest)
from .fault_tolerance.plan import InjectedFault, fault_point
from .fault_tolerance.watchdog import CollectiveTimeoutError

__all__ = ["DeviceLostError", "ElasticTrainer", "elastic_state_dict",
           "run_elastic_drill"]

_SNAP_PREFIX = "snap_"
_STAGING_ITEM = "elastic.snapshot"


class DeviceLostError(RuntimeError):
    """A device (or the whole host) dropped out of the training mesh.

    ``lost_ranks``: flat mesh indices of the lost devices (empty when
    the whole host was preempted).  ``preempted``: True for a host-level
    preemption notice — recovery restarts on the same topology instead
    of shrinking.
    """

    def __init__(self, lost_ranks, reason="", preempted=False):
        self.lost_ranks = sorted(set(int(r) for r in lost_ranks))
        self.reason = reason or "device lost"
        self.preempted = bool(preempted)
        what = ("host preempted" if preempted
                else f"device(s) lost: ranks {self.lost_ranks}")
        super().__init__(f"{what} ({self.reason})")


def elastic_state_dict(model, optimizer=None):
    """The ``{name: Tensor}`` training state an :class:`ElasticTrainer`
    snapshots: named parameters plus (prefixed) optimizer accumulators
    and the step counter.  Names are stable across a recovery because
    the same live objects are restored in place."""
    from ..core.tensor import Tensor
    state = {}
    for name, p in model.named_parameters():
        state[name] = p
    if optimizer is not None:
        for key, t in optimizer.state_dict().items():
            if isinstance(t, Tensor):
                state[f"opt::{key}"] = t
    return state


def _rng_state_host():
    from ..framework import random as _random
    return np.asarray(_random.default_generator().get_state()._value)


def _set_rng_state_host(key):
    from ..framework import random as _random
    arr = np.asarray(key, dtype=np.uint32)
    _random.default_generator().set_state(arr)


# ---------------------------------------------------------------------------
# Async snapshots
# ---------------------------------------------------------------------------

def _capture_host_state(state_dict):
    """Device->host copy of every tensor (the staging buffer): a
    consistent point-in-time image, synchronizing each fetch."""
    host, meta, nbytes = {}, {}, 0
    for name, t in state_dict.items():
        arr = np.asarray(t._value)
        host[name] = arr
        meta[name] = {"type": "tensor",
                      "global_shape": list(arr.shape),
                      "dtype": arr.dtype.name}
        nbytes += arr.nbytes
    return host, meta, nbytes


def _write_snapshot(path, host, meta, train_meta):
    """Background-thread body: crash-safe snapshot commit through the
    atomic tmp+rename+sha256-manifest path (save_state_dict layout, so
    ``checkpoint.load_state_dict`` can read it too)."""
    os.makedirs(path, exist_ok=True)
    fault_point("elastic.snapshot.write", path=path)
    shards = {name: [{"index": [[0, d] for d in arr.shape],
                      "data": arr}]
              for name, arr in host.items()}
    with atomic_write(os.path.join(path, "shard_0.pkl")) as f:
        pickle.dump(shards, f)
    with atomic_write(os.path.join(path, "metadata.json"), "w") as f:
        json.dump(meta, f)
    write_manifest(path, extra={"train": dict(train_meta)})


def read_train_meta(path):
    """The manifest's ``"train"`` block, or ``None``."""
    try:
        with open(os.path.join(path, MANIFEST_NAME)) as f:
            return json.load(f).get("train")
    except (OSError, ValueError):
        return None


def list_snapshots(ckpt_dir):
    """Snapshot directories under ``ckpt_dir``, newest last."""
    if not os.path.isdir(ckpt_dir):
        return []
    return sorted(os.path.join(ckpt_dir, n)
                  for n in os.listdir(ckpt_dir)
                  if n.startswith(_SNAP_PREFIX)
                  and os.path.isdir(os.path.join(ckpt_dir, n)))


# ---------------------------------------------------------------------------
# The supervisor
# ---------------------------------------------------------------------------

class ElasticTrainer:
    """Run a static training program step-by-step, surviving device loss.

    ``feed_fn(step) -> feed dict`` is the data loader; ``step`` is the
    cursor recorded in every snapshot manifest, so resume re-reads
    exactly the batches the lost run would have.

    ``state_dict``: ``{name: Tensor}`` (see :func:`elastic_state_dict`)
    — snapshotted asynchronously every ``snapshot_every`` steps and
    restored in place on recovery.
    """

    def __init__(self, exe, program, feed_fn, fetch_list, *, state_dict,
                 ckpt_dir, snapshot_every=0, keep=2, manager=None,
                 max_restarts=2):
        self.exe = exe
        self.program = program
        self.feed_fn = feed_fn
        self.fetch_list = fetch_list
        self.state_dict = dict(state_dict)
        self.ckpt_dir = ckpt_dir
        self.snapshot_every = int(snapshot_every)
        self.keep = max(1, int(keep))
        self.manager = manager
        self.max_restarts = int(max_restarts)
        self.restarts = 0
        self.lost_steps = 0
        self.mttr_ms = []
        self.recovery_to_first_step_ms = None
        self.last_resume_path = None
        self.last_resume_step = None
        self._writer = None
        self._writer_err = None
        self._recovered_at = None

    # -- detection --------------------------------------------------------
    def _world(self):
        plan = get_mesh_plan()
        return plan.size if plan is not None else 1

    def _probe_health(self):
        """Fault-site probes + heartbeat staleness, every step boundary."""
        try:
            fault_point("dist.host_preempt")
        except InjectedFault as e:
            raise DeviceLostError([], reason=str(e) or "host_preempt",
                                  preempted=True) from e
        for r in range(self._world()):
            try:
                fault_point(f"dist.device_lost.{r}")
            except InjectedFault as e:
                raise DeviceLostError([r], reason=str(e) or
                                      "device_lost") from e
        if self.manager is not None:
            dead = self.manager.dead_ranks()
            if dead:
                raise DeviceLostError(dead, reason="heartbeat staleness")

    @staticmethod
    def _escalate(exc):
        """Map a raw failure raised out of a step into DeviceLostError."""
        if isinstance(exc, DeviceLostError):
            return exc
        if isinstance(exc, CollectiveTimeoutError):
            return DeviceLostError(exc.missing or [],
                                   reason=f"collective watchdog: {exc}",
                                   preempted=not exc.missing)
        return DeviceLostError([], reason=str(exc), preempted=True)

    # -- snapshots --------------------------------------------------------
    def _snapshot_due(self, completed):
        return (self.snapshot_every > 0 and completed > 0
                and completed % self.snapshot_every == 0)

    def snapshot(self, completed):
        """Capture on the caller's thread, commit on a background one."""
        self._join_writer()
        with obs.span("ckpt:snapshot", cat="ckpt", step=completed):
            _pipeline.drain()
            host, meta, nbytes = _capture_host_state(self.state_dict)
            train_meta = {"step": int(completed),
                          "rng_key": _rng_state_host().tolist(),
                          "data_cursor": int(completed)}
        register_resident(_STAGING_ITEM, nbytes, host=True)
        path = os.path.join(self.ckpt_dir,
                            f"{_SNAP_PREFIX}{completed:08d}")

        def _body():
            try:
                with obs.span("ckpt:write", cat="ckpt", step=completed,
                              bytes=nbytes):
                    _write_snapshot(path, host, meta, train_meta)
                if self.manager is not None:
                    try:
                        self.manager.record_checkpoint(
                            path, int(completed), validate=False)
                    except Exception:
                        pass
                self._prune()
            except BaseException as e:  # surfaced on next join
                self._writer_err = e
            finally:
                unregister_resident(_STAGING_ITEM, host=True)

        self._writer = threading.Thread(
            target=_body, name="elastic-snapshot", daemon=True)
        self._writer.start()
        return path

    def _join_writer(self):
        w, self._writer = self._writer, None
        if w is not None:
            w.join()
        err, self._writer_err = self._writer_err, None
        if err is not None:
            import warnings
            warnings.warn(f"async snapshot failed: {err!r}",
                          RuntimeWarning, stacklevel=2)

    def _prune(self):
        snaps = list_snapshots(self.ckpt_dir)
        for path in snaps[: max(0, len(snaps) - self.keep)]:
            shutil.rmtree(path, ignore_errors=True)

    # -- recovery ---------------------------------------------------------
    def _surviving_devices(self, plan, lost_ranks):
        devs = list(np.asarray(plan.mesh.devices).ravel())
        return [d for i, d in enumerate(devs) if i not in set(lost_ranks)]

    def _pick_checkpoint(self):
        """Newest *valid* snapshot; invalid ones are skipped with a
        recorded ``ckpt.corrupt`` instant (torn write / bit-rot)."""
        for path in reversed(list_snapshots(self.ckpt_dir)):
            ok, reasons = validate_checkpoint(path)
            if ok:
                return path
            if obs.enabled():
                obs.instant("ckpt.corrupt", cat="fault", path=path,
                            reasons="; ".join(reasons))
        return None

    def restore(self, path, plan=None):
        """Re-place the snapshot under ``plan`` (default: active plan)
        and restore step counter / RNG / cursor from its manifest.
        Returns the step to resume from."""
        import jax.numpy as jnp
        plan = plan if plan is not None else get_mesh_plan()
        with open(os.path.join(path, "metadata.json")) as f:
            meta = json.load(f)
        all_shards = {}
        for fname in sorted(os.listdir(path)):
            if fname.startswith("shard_") and fname.endswith(".pkl"):
                with open(os.path.join(path, fname), "rb") as f:
                    for name, pieces in pickle.load(f).items():
                        all_shards.setdefault(name, []).extend(pieces)
        named_shapes = {n: tuple(m["global_shape"])
                        for n, m in meta.items() if m["type"] == "tensor"}
        shard_fns = {}
        if plan is not None and not plan.is_virtual:
            shard_fns, _ = make_shard_and_gather_fns(plan, named_shapes)
        for name, t in self.state_dict.items():
            m = meta.get(name)
            if m is None or m["type"] != "tensor":
                continue
            full = np.zeros(m["global_shape"],
                            np.float32 if m["dtype"] == "bfloat16"
                            else np.dtype(m["dtype"]))
            for piece in all_shards.get(name, []):
                idx = tuple(slice(a, b) for a, b in piece["index"])
                full[idx] = piece["data"]
            val = jnp.asarray(full, t._value.dtype)
            if name in shard_fns:
                val = shard_fns[name](val)
            t._inplace_update(val)
        train = read_train_meta(path) or {}
        if train.get("rng_key") is not None:
            _set_rng_state_host(train["rng_key"])
        return int(train.get("step", 0))

    def _recover(self, err, failed_step):
        t0 = time.perf_counter()
        if obs.enabled():
            obs.instant("elastic.device_lost", cat="recovery",
                        ranks=",".join(map(str, err.lost_ranks)),
                        preempted=err.preempted, step=failed_step,
                        reason=err.reason)
        reg = obs.get_registry()
        reg.counter("elastic.restarts").inc()
        self.restarts += 1
        if self.restarts > self.max_restarts:
            raise err
        # abort the step cleanly: no leaked in-flight buffers, staging
        # line item released even if the writer died mid-commit
        with obs.span("recovery:abort", cat="recovery"):
            try:
                _pipeline.drain()
            except Exception:
                pass
            self._join_writer()
            try:
                unregister_resident(_STAGING_ITEM, host=True)
            except Exception:
                pass
        plan = get_mesh_plan()
        if plan is not None and err.lost_ranks and not err.preempted:
            with obs.span("recovery:shrink", cat="recovery",
                          mesh=plan.describe()):
                survivors = self._surviving_devices(plan, err.lost_ranks)
                plan = plan.shrink(survivors)
                set_mesh_plan(plan)
        path = self._pick_checkpoint()
        if path is None:
            raise DeviceLostError(
                err.lost_ranks,
                reason=f"{err.reason}; no valid snapshot to resume from",
                preempted=err.preempted)
        with obs.span("recovery:restore", cat="recovery", path=path):
            resume = self.restore(path, plan)
        self.last_resume_path = path
        self.last_resume_step = resume
        lost = max(0, failed_step - resume)
        self.lost_steps += lost
        reg.counter("elastic.lost_steps").inc(lost)
        ms = (time.perf_counter() - t0) * 1e3
        self.mttr_ms.append(ms)
        reg.histogram("elastic.mttr_ms").observe(ms)
        self._recovered_at = t0
        return resume

    # -- the loop ---------------------------------------------------------
    def run(self, n_steps, start_step=0):
        """Supervised training loop: ``start_step .. n_steps-1``, with
        health probes, periodic async snapshots, and recovery.  Returns
        the last step's fetches as numpy."""
        step = int(start_step)
        outs = None
        while step < n_steps:
            try:
                self._probe_health()
                outs = self.exe.run(self.program,
                                    feed=self.feed_fn(step),
                                    fetch_list=self.fetch_list,
                                    return_numpy=False)
                step += 1
                if self._recovered_at is not None:
                    _pipeline.drain()
                    self.recovery_to_first_step_ms = round(
                        (time.perf_counter() - self._recovered_at) * 1e3,
                        3)
                    self._recovered_at = None
                if self._snapshot_due(step):
                    self.snapshot(step)
            except (DeviceLostError, CollectiveTimeoutError,
                    InjectedFault) as e:
                step = self._recover(self._escalate(e), step)
                outs = None
        _pipeline.drain()
        self._join_writer()
        return [np.asarray(o) for o in outs] if outs else outs

    def stats(self):
        return {"restarts": self.restarts,
                "lost_steps": self.lost_steps,
                "mttr_ms": [round(v, 3) for v in self.mttr_ms],
                "recovery_to_first_step_ms":
                    self.recovery_to_first_step_ms}


# ---------------------------------------------------------------------------
# The chaos drill (shared by scripts/chaos_smoke.py and the tests)
# ---------------------------------------------------------------------------

def run_elastic_drill(n_steps=8, kill_step=5, kill_rank=3,
                      snapshot_every=2, seed=7, ckpt_dir=None):
    """Kill a device mid-run on a dp=4 host mesh, shrink to dp=2,
    restore, resume — and assert bit-parity against a clean run started
    from the same checkpoint on the shrunk mesh.

    Needs >= 4 jax devices (use ``--xla_force_host_platform_device_count``).
    Returns a report dict; ``report["ok"]`` is the gate verdict.
    """
    import tempfile

    import jax
    import paddle_tpu as paddle
    from paddle_tpu import optimizer as popt
    from paddle_tpu import static
    from paddle_tpu.models import BertConfig, BertForMaskedLM
    from .auto_parallel.sharding import (BERT_RULES, MeshPlan,
                                         annotate_params, clear_mesh_plan)
    from .fault_tolerance.plan import FaultPlan, inject
    from ..memory.guard import host_resident_items
    from ..static.executor import Executor

    if jax.device_count() < 4:
        raise RuntimeError(
            f"elastic drill needs >= 4 devices, have {jax.device_count()};"
            " set XLA_FLAGS=--xla_force_host_platform_device_count=8")
    B, S, V = 8, 16, 256
    tmp = None
    if ckpt_dir is None:
        tmp = tempfile.mkdtemp(prefix="elastic_drill_")
        ckpt_dir = tmp

    def _feed(step):
        rng = np.random.default_rng(seed * 7919 + step)
        return {"ids": rng.integers(0, V, (B, S)).astype(np.int64),
                "labels": rng.integers(0, V, (B, S)).astype(np.int64)}

    def _build(plan):
        """Fresh program + model + optimizer under ``plan``."""
        set_mesh_plan(plan)
        main_prog, startup = static.Program(), static.Program()
        with static.program_guard(main_prog, startup):
            ids = static.data("ids", [B, S], "int64")
            labels = static.data("labels", [B, S], "int64")
            model = BertForMaskedLM(BertConfig(
                vocab_size=V, hidden_size=32, num_hidden_layers=1,
                num_attention_heads=2, intermediate_size=64,
                max_position_embeddings=S))
            annotate_params(model)
            loss, _ = model(ids, labels=labels)
            opt = popt.AdamW(learning_rate=1e-3,
                             parameters=model.parameters())
            opt.minimize(loss)
        exe = Executor()
        exe.run(startup)
        # materialize moment accumulators now (normally lazy, first
        # dispatch) so the snapshot state_dict covers them from step 0
        opt._ensure_static_state(
            [p for p in model.parameters() if not p.stop_gradient])
        return exe, main_prog, model, opt, loss

    paddle.enable_static()
    report = {}
    try:
        paddle.seed(seed)
        plan = MeshPlan("dp=4", rules=BERT_RULES())
        exe, prog, model, opt, loss = _build(plan)
        state = elastic_state_dict(model, opt)
        trainer = ElasticTrainer(
            exe, prog, _feed, [loss], state_dict=state,
            ckpt_dir=ckpt_dir, snapshot_every=snapshot_every,
            keep=max(8, n_steps))
        fp = FaultPlan()
        fp.add(f"dist.device_lost.{kill_rank}", "kill",
               after=kill_step, count=1)
        t0 = time.perf_counter()
        with inject(fp):
            outs = trainer.run(n_steps)
        elastic_wall_s = time.perf_counter() - t0
        shrunk = get_mesh_plan()
        elastic_params = {n: np.asarray(t._value)
                          for n, t in state.items()}
        stats = trainer.stats()
        window_len = len(_pipeline.get_window())
        leaked_host = [n for n, _ in host_resident_items()
                       if n == _STAGING_ITEM]

        # clean reference: a FRESH model/program on the shrunk topology,
        # restored from the SAME snapshot the recovery used, run to the
        # same final step — final state must be bit-identical
        resume_path = trainer.last_resume_path
        clear_mesh_plan()
        Executor.clear_shared_cache()
        paddle.seed(seed)
        plan2 = MeshPlan(dict(shrunk.axis_sizes), rules=BERT_RULES(),
                         devices=list(
                             np.asarray(shrunk.mesh.devices).ravel()))
        exe2, prog2, model2, opt2, loss2 = _build(plan2)
        state2 = elastic_state_dict(model2, opt2)
        # positional rename: fresh session counters give the clean
        # model different auto-generated names; order is identical
        remap = dict(zip(state2.keys(), state.keys()))
        state2 = {remap[k]: t for k, t in state2.items()}
        ref = ElasticTrainer(exe2, prog2, _feed, [loss2],
                             state_dict=state2, ckpt_dir=ckpt_dir,
                             snapshot_every=0)
        resume = ref.restore(resume_path, plan2)
        for step in range(resume, n_steps):
            exe2.run(prog2, feed=_feed(step), fetch_list=[loss2])
        clean_params = {n: np.asarray(t._value)
                        for n, t in state2.items()}

        mismatch = [n for n in elastic_params
                    if n in clean_params
                    and elastic_params[n].tobytes()
                    != clean_params[n].tobytes()]
        parity = not mismatch and len(elastic_params) == len(clean_params)
        phases = obs.phase_breakdown() if obs.enabled() else {}
        report = {
            "ok": bool(parity and stats["restarts"] == 1
                       and window_len == 0 and not leaked_host
                       and shrunk.axis_size("dp") == 2
                       and resume == trainer.last_resume_step
                       and resume < n_steps),
            "parity": parity,
            "mismatched_params": mismatch[:5],
            "mesh_before": "dp=4",
            "mesh_after": shrunk.describe(),
            "resume_step": trainer.last_resume_step,
            "replayed_steps": n_steps - resume,
            "restarts": stats["restarts"],
            "lost_steps": stats["lost_steps"],
            "mttr_ms": stats["mttr_ms"],
            "recovery_to_first_step_ms":
                stats["recovery_to_first_step_ms"],
            "window_len": window_len,
            "leaked_host_items": leaked_host,
            "elastic_wall_s": round(elastic_wall_s, 3),
            "final_loss": float(np.asarray(outs[0])) if outs else None,
            "phases": phases,
        }
        return report
    finally:
        clear_mesh_plan()
        Executor.clear_shared_cache()
        paddle.disable_static()
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
